"""Hypothesis property tests on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import crest, quant, sparsity

jax.config.update("jax_platform_name", "cpu")

_seeds = st.integers(0, 2**31 - 1)


# ---------------------------------------------------------------------------
# quantization invariants
# ---------------------------------------------------------------------------

@settings(max_examples=50, deadline=None)
@given(_seeds)
def test_quantization_idempotent(seed):
    """q(deq(q(w))) == q(w): the FP4 grid is a fixed point."""
    w = jax.random.normal(jax.random.PRNGKey(seed), (32, 8)) * 0.3
    p1, s1 = quant.quantize_weight(w)
    w1 = quant.dequantize_weight(p1, s1, jnp.float32)
    p2, s2 = quant.quantize_weight(w1)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


@settings(max_examples=50, deadline=None)
@given(_seeds, st.floats(0.125, 8.0))
def test_quantization_scale_equivariance(seed, scale):
    """Scaling a weight matrix scales its dequantized form (absmax scales
    pass through): deq(q(s*w)) == s * deq(q(w))."""
    w = jax.random.normal(jax.random.PRNGKey(seed), (16, 4))
    a = quant.dequantize_weight(*quant.quantize_weight(w * scale), jnp.float32)
    b = quant.dequantize_weight(*quant.quantize_weight(w), jnp.float32) * scale
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)


@settings(max_examples=100, deadline=None)
@given(st.lists(st.integers(0, 15), min_size=2, max_size=64).filter(lambda l: len(l) % 2 == 0))
def test_pack_unpack_roundtrip_any_codes(codes):
    c = jnp.asarray(codes, jnp.uint8)[:, None]
    assert bool(jnp.all(quant.unpack_fp4(quant.pack_fp4(c, 0), 0) == c))


@settings(max_examples=100, deadline=None)
@given(st.floats(0.0, 400.0), st.floats(0.0, 400.0))
def test_fp8_truncation_monotone(a, b):
    """Round-toward-zero is monotone on non-negatives."""
    lo, hi = sorted([a, b])
    ta = float(quant.fp8_e4m3_truncate(jnp.float32(lo)))
    tb = float(quant.fp8_e4m3_truncate(jnp.float32(hi)))
    assert ta <= tb


@settings(max_examples=50, deadline=None)
@given(_seeds)
def test_fake_quant_zero_gradient_residual(seed):
    """STE: grad(mean(fq(w))) == grad(mean(w)) exactly."""
    w = jax.random.normal(jax.random.PRNGKey(seed), (8, 8))
    g1 = jax.grad(lambda w: jnp.mean(quant.fake_quant_fp4(w)))(w)
    g2 = jax.grad(lambda w: jnp.mean(w))(w)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-7)


# ---------------------------------------------------------------------------
# sparsity invariants (paper Section 10.13)
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(_seeds, st.sampled_from([0.1, 0.25, 0.5, 0.9]))
def test_topk_sparsity_density_and_idempotence(seed, density):
    w = jax.random.normal(jax.random.PRNGKey(seed), (64, 8))
    ws, mask = sparsity.topk_sparsify(w, density)
    kept = float(jnp.mean(jnp.sum(mask, axis=0) / 64))
    assert abs(kept - density) < 0.05
    ws2, _ = sparsity.topk_sparsify(ws, density)
    np.testing.assert_array_equal(np.asarray(ws), np.asarray(ws2))
    # kept entries are untouched; dropped entries are exactly zero
    np.testing.assert_array_equal(np.asarray(ws)[np.asarray(mask)],
                                  np.asarray(w)[np.asarray(mask)])
    assert np.all(np.asarray(ws)[~np.asarray(mask)] == 0)


def test_sparsity_activity_factor_matches_paper():
    """Table 5: alpha = 0.10*(1-s) + 0.04*s = 0.046 at s=0.90."""
    w = jnp.ones((100, 10)).at[: 90].set(0.0)
    stats = sparsity.sparsity_stats(w)
    assert abs(stats["sparsity"] - 0.9) < 1e-6
    assert abs(stats["activity_factor"] - 0.046) < 1e-6


# ---------------------------------------------------------------------------
# CREST invariants
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(_seeds, st.integers(0, 4))
def test_crest_eventually_detects_any_fault_set(seed, n_faults):
    """For any fault set with <= n_spares faults, enough probe cycles detect
    and repair every fault with zero false positives."""
    cfg = crest.CrestConfig(n_spares=4, threshold=2)
    n, k = 24, 8
    w = jax.random.normal(jax.random.PRNGKey(seed), (k, n))
    fault = (crest.inject_column_faults(jax.random.PRNGKey(seed + 1), n, n_faults)
             if n_faults else jnp.zeros((n,), bool))
    state = crest.crest_init(n, cfg)
    step = jax.jit(lambda x, s: crest.crest_matmul(x, w, s, cfg, fault if n_faults else None))
    cycles = (n // cfg.n_spares) * (cfg.threshold + 1) + 2
    for i in range(cycles):
        x = jax.random.normal(jax.random.PRNGKey(1000 + seed + i), (4, k))
        y, state = step(x, state)
    stats = crest.coverage_stats(state, fault)
    assert stats["detected"] == n_faults
    assert stats["false_positives"] == 0
    assert stats["repaired"] == n_faults


# ---------------------------------------------------------------------------
# CASCADE schedule invariants (paper Table 6 model)
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.integers(1, 18), st.integers(1, 8), st.integers(1, 6))
def test_cascade_schedule_efficiency_monotone_in_batches(log2_batches, rows_k, arrays_k):
    import sys, os
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks.paper_tables import cascade_schedule
    rows_per = 64
    rows_total = rows_per * 64 * arrays_k
    s1 = cascade_schedule(batches=2 ** log2_batches, rows_total=rows_total,
                          cols=1024 * rows_k, rows_per_array=rows_per)
    s2 = cascade_schedule(batches=2 ** log2_batches * 2, rows_total=rows_total,
                          cols=1024 * rows_k, rows_per_array=rows_per)
    # pipeline fill amortizes: efficiency strictly increases with batches
    assert s2["efficiency"] > s1["efficiency"]
    assert s2["efficiency"] < 1.0
    # cycles are affine in batches with unit slope
    assert s2["total_cycles"] - s1["total_cycles"] == 2 ** log2_batches


# ---------------------------------------------------------------------------
# data pipeline invariants
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(_seeds, st.integers(0, 10_000))
def test_data_pipeline_pure_function_of_step(seed, step):
    from repro.data.pipeline import DataConfig, SyntheticCorpus
    cfg = DataConfig(vocab=64, seq_len=8, global_batch=2, seed=seed % 1000)
    a = SyntheticCorpus(cfg).batch_at(step)
    b = SyntheticCorpus(cfg).batch_at(step)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].min() >= 0 and a["tokens"].max() < 64


# ---------------------------------------------------------------------------
# sharding-policy invariants
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.sampled_from(["cascade", "megatron"]),
       st.sampled_from(["qwen2.5-32b", "olmoe-1b-7b", "recurrentgemma-2b"]))
def test_param_specs_rank_safe(policy, arch):
    """Every generated PartitionSpec has rank <= leaf rank, and mentions only
    mesh axes (no stale names)."""
    from repro.core.cascade import CascadeConfig
    from repro.distributed import sharding as shd
    from repro.models import registry
    cfg, model = registry.load(arch, smoke=True)
    pshape = jax.eval_shape(lambda: model.init_params(
        jax.random.PRNGKey(0), CascadeConfig(mode="train")))
    specs = shd.param_specs(pshape, policy)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "_normalized_spec") or
                             type(x).__name__ == "PartitionSpec")
    flat_l = jax.tree.leaves(pshape)
    assert len(flat_s) == len(flat_l)
    for sp, lf in zip(flat_s, flat_l):
        assert len(sp) <= lf.ndim, (sp, lf.shape)
        for part in sp:
            if part is None:
                continue
            parts = part if isinstance(part, tuple) else (part,)
            assert set(parts) <= {"pod", "data", "model"}


@settings(max_examples=30, deadline=None)
@given(_seeds, st.sampled_from([0.05, 0.1, 0.25]))
def test_sparsity_survives_fp4_quantization(seed, density):
    """Paper Sections 4 + 10.13: Top-K sparsity composes with FP4 PTQ —
    zero is exactly representable in E2M1, so every pruned weight stays
    exactly zero through quantize->dequantize (the power-saving zeros are
    preserved in the serving format)."""
    w = jax.random.normal(jax.random.PRNGKey(seed), (64, 8))
    ws, mask = sparsity.topk_sparsify(w, density)
    wq = quant.dequantize_weight(*quant.quantize_weight(ws), jnp.float32)
    assert np.all(np.asarray(wq)[~np.asarray(mask)] == 0.0)
    stats = sparsity.sparsity_stats(wq)
    assert stats["sparsity"] >= 1.0 - density - 1e-6


@settings(max_examples=25, deadline=None)
@given(_seeds, st.integers(1, 9), st.integers(3, 130), st.integers(1, 40))
def test_packed_matmul_matches_ref_random_shapes(seed, m, k, n):
    """ops.cascade_matmul on arbitrary (M, K, N) — odd K included, which
    exercises quantize_weight's zero-row pad-to-pack and the matching
    activation pad — agrees with the ref.py dequant-matmul oracle."""
    from repro.kernels import ops
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    w = jax.random.normal(keys[0], (k, n)) * 0.2
    packed, scales = quant.quantize_weight(w)
    x = jax.random.normal(keys[1], (m, k))
    out = ops.cascade_matmul(x, packed, scales, interpret=True)
    ref = ops.cascade_matmul_ref(x, packed, scales)
    assert out.shape == (m, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@settings(max_examples=50, deadline=None)
@given(st.lists(st.integers(0, 15), min_size=16, max_size=16))
def test_fp4_code_lists_roundtrip_through_values(codes):
    """Arbitrary code vectors survive decode -> encode bit-exactly (the
    16-point E2M1 grid is a codec fixed point, signs included)."""
    c = jnp.asarray(codes, jnp.uint8)[:, None]
    assert bool(jnp.all(quant.fp4_encode(quant.fp4_decode(c)) == c))
