"""Substrate tests: data pipeline, optimizer, checkpoint/restart, gradient
compression, CREST, sharding specs, serve engine + elastic failover."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_data_deterministic_and_host_sharded():
    from repro.data.pipeline import DataConfig, SyntheticCorpus
    c1 = SyntheticCorpus(DataConfig(vocab=128, seq_len=32, global_batch=8))
    c2 = SyntheticCorpus(DataConfig(vocab=128, seq_len=32, global_batch=8))
    b1, b2 = c1.batch_at(7), c2.batch_at(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])
    # different hosts produce different shards
    ch = SyntheticCorpus(DataConfig(vocab=128, seq_len=32, global_batch=8,
                                    host_id=1, n_hosts=2))
    assert ch.local_batch == 4
    assert not np.array_equal(ch.batch_at(7)["tokens"], b1["tokens"][:4])


def test_data_has_learnable_structure():
    from repro.data.pipeline import DataConfig, SyntheticCorpus
    c = SyntheticCorpus(DataConfig(vocab=64, seq_len=256, global_batch=4))
    b = c.batch_at(0)
    # Markov structure: successor entropy given token < unigram entropy
    toks = b["tokens"].reshape(-1)
    # top-1 bigram predictability must beat uniform chance by a wide margin
    pairs = {}
    for a, bb in zip(toks[:-1], toks[1:]):
        pairs.setdefault(int(a), []).append(int(bb))
    hits = tot = 0
    for a, succ in pairs.items():
        if len(succ) < 4:
            continue
        vals, counts = np.unique(succ, return_counts=True)
        hits += counts.max()
        tot += len(succ)
    assert hits / tot > 0.15, "corpus has no learnable bigram structure"


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_converges_on_quadratic():
    from repro.optim.adamw import AdamW
    opt = AdamW(lr=0.1, weight_decay=0.0, warmup_steps=1, decay_steps=200)
    params = {"w": jnp.array([3.0, -2.0])}
    state = opt.init(params)
    for _ in range(150):
        grads = jax.tree.map(lambda p: 2 * p, params)
        params, state, m = opt.update(grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.1


def test_adamw_weight_decay_mask():
    from repro.optim.adamw import AdamW
    opt = AdamW(lr=1e-2, weight_decay=1.0, warmup_steps=1)
    params = {"w": jnp.ones((4, 4)), "scale": jnp.ones((4,))}
    state = opt.init(params)
    zero_grads = jax.tree.map(jnp.zeros_like, params)
    p2, _, _ = opt.update(zero_grads, state, params)
    assert float(p2["w"].max()) < 1.0        # decayed (ndim >= 2)
    assert float(p2["scale"].min()) == 1.0   # not decayed (1-D)


# ---------------------------------------------------------------------------
# checkpoint / restart
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_and_latest(tmp_path):
    from repro.train import checkpoint as ckpt
    tree = {"a": jnp.arange(6).reshape(2, 3), "n": {"b": jnp.float32(3.5)},
            "l": [jnp.ones(2), jnp.zeros(3)]}
    ckpt.save(tree, str(tmp_path), 10, extra={"data_step": 10})
    ckpt.save(jax.tree.map(lambda x: x + 1, tree), str(tmp_path), 20,
              extra={"data_step": 20})
    assert ckpt.latest_step(str(tmp_path)) == 20
    restored, extra = ckpt.restore(tree, str(tmp_path))
    assert extra["data_step"] == 20
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree["a"]) + 1)


def test_checkpoint_async_and_atomic(tmp_path):
    from repro.train import checkpoint as ckpt
    tree = {"w": jnp.ones((32, 32))}
    t = ckpt.save(tree, str(tmp_path), 5, async_=True)
    t.join()
    assert ckpt.latest_step(str(tmp_path)) == 5
    # a stale .tmp dir must not be considered a checkpoint
    os.makedirs(tmp_path / "step_99.tmp", exist_ok=True)
    assert ckpt.latest_step(str(tmp_path)) == 5


def test_train_restart_bit_identical(tmp_path):
    """Fault-tolerance invariant: save at step k, 'crash', restore, continue
    => identical loss trajectory to an uninterrupted run."""
    from repro.core.cascade import CascadeConfig
    from repro.data.pipeline import DataConfig, SyntheticCorpus
    from repro.models import registry
    from repro.optim.adamw import AdamW
    from repro.train import checkpoint as ckpt
    from repro.train import loop as train_loop

    cfg, model = registry.load("olmoe-1b-7b", smoke=True)
    ccfg = CascadeConfig(mode="train", compute_dtype=jnp.float32)
    opt = AdamW(lr=1e-3, warmup_steps=2, decay_steps=10)
    data = SyntheticCorpus(DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=2))
    step_fn = jax.jit(train_loop.make_train_step(model, ccfg, opt, remat=False))

    state = train_loop.init_state(model, ccfg, opt)
    losses_a = []
    for i in range(6):
        if i == 3:
            ckpt.save(state, str(tmp_path), i, extra={"data_step": i})
        state, m = step_fn(state, jax.tree.map(jnp.asarray, data.batch_at(i)))
        losses_a.append(float(m["loss"]))

    # crash + restore at step 3
    state_b = train_loop.init_state(model, ccfg, opt)
    state_b, extra = ckpt.restore(state_b, str(tmp_path))
    losses_b = []
    for i in range(int(extra["data_step"]), 6):
        state_b, m = step_fn(state_b, jax.tree.map(jnp.asarray, data.batch_at(i)))
        losses_b.append(float(m["loss"]))
    np.testing.assert_allclose(losses_a[3:], losses_b, rtol=1e-6)


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_grad_compression_error_feedback_unbiased(seed):
    """With error feedback, the accumulated compressed sum converges to the
    true sum: residual stays bounded by one quantization step."""
    from repro.optim import grad_compression as gc
    key = jax.random.PRNGKey(seed)
    g = jax.random.normal(key, (64,))
    r = jnp.zeros((64,))
    total = jnp.zeros((64,))
    for i in range(8):
        q, scale, r = gc.compress(g, r)
        total = total + gc.decompress(q, scale)
    # sum of 8 compressed reps ~ 8*g, residual bounded
    np.testing.assert_allclose(np.asarray(total + r), np.asarray(8 * g), rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(r).max()) <= float(scale) + 1e-6


def test_grad_compression_allreduce_shardmap():
    from functools import partial
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.optim import grad_compression as gc

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    g = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 8))}
    r = gc.init_residuals(g)

    @partial(shard_map, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
             check_rep=False)
    def f(g, r):
        return gc.allreduce_compressed(g, r, "data")

    out, new_r = f(g, r)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                               atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------------------------
# CREST
# ---------------------------------------------------------------------------

def test_crest_detects_and_repairs_all_faults():
    from repro.core import crest
    cfg = crest.CrestConfig(n_spares=4, threshold=2)
    n, k, m = 32, 16, 8
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n))
    fault = crest.inject_column_faults(jax.random.PRNGKey(1), n, 3)
    state = crest.crest_init(n, cfg)
    step = jax.jit(lambda x, s: crest.crest_matmul(x, w, s, cfg, fault))
    for i in range(40):
        x = jax.random.normal(jax.random.PRNGKey(100 + i), (m, k))
        y, state = step(x, state)
    stats = crest.coverage_stats(state, fault)
    assert stats["detected"] == 3 and stats["false_positives"] == 0
    # post-repair output matches the clean matmul everywhere
    x = jax.random.normal(jax.random.PRNGKey(999), (m, k))
    y, _ = step(x, state)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), atol=1e-4)


def test_crest_healthy_path_is_exact_and_stateless():
    from repro.core import crest
    cfg = crest.CrestConfig(n_spares=2, threshold=3)
    w = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
    state = crest.crest_init(16, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
    y, s2 = crest.crest_matmul(x, w, state, cfg, None)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), atol=1e-5)
    assert int(s2.confirmed_faults.sum()) == 0
    assert int(s2.n_repaired) == 0


def test_crest_transient_errors_filtered():
    """A fault that appears once (cosmic ray) then disappears must NOT be
    confirmed (threshold consecutive-mismatch filter, paper Section 20.2)."""
    from repro.core import crest
    cfg = crest.CrestConfig(n_spares=16, threshold=3)  # test all cols each step
    n, k = 16, 8
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n))
    state = crest.crest_init(n, cfg)
    transient = jnp.zeros((n,), bool).at[5].set(True)
    for i in range(6):
        x = jax.random.normal(jax.random.PRNGKey(i), (4, k))
        mask = transient if i == 2 else None  # single-step glitch
        _, state = jax.jit(lambda x, s, fm: crest.crest_matmul(x, w, s, cfg, fm),
                           static_argnums=())(x, state, mask) if False else \
            crest.crest_matmul(x, w, state, cfg, mask)
    assert int(state.confirmed_faults.sum()) == 0


# ---------------------------------------------------------------------------
# sharding specs
# ---------------------------------------------------------------------------

def test_param_specs_cascade_never_shards_contraction():
    """CASCADE policy invariant (the paper's core claim): no weight is sharded
    on its contraction dim => no partial-sum all-reduce can exist."""
    from jax.sharding import PartitionSpec as P
    from repro.core.cascade import CascadeConfig
    from repro.distributed import sharding as shd
    from repro.models import registry

    for arch in ["qwen2.5-32b", "deepseek-v2-236b", "mamba2-370m"]:
        cfg, model = registry.load(arch, smoke=True)
        ccfg = CascadeConfig(mode="train", compute_dtype=jnp.float32)
        pshape = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), ccfg))
        specs = shd.param_specs(pshape, "cascade")
        flat, _ = jax.tree_util.tree_flatten_with_path(specs)
        for path, spec in flat:
            names = [str(getattr(k, "key", "")) for k in path]
            if names[-1] == "w" and "model" in str(spec):
                if spec[-1] in ("model", ("model",)):
                    # column-parallel: model on output dim only
                    assert all(s != "model" for s in spec[:-1]), (names, spec)
                else:
                    # expert-parallel: model on the E dim; both matmul dims
                    # (contraction K and output N) stay local
                    assert spec[-3] == "model", (names, spec)
                    assert spec[-1] is None and spec[-2] is None, (names, spec)


def test_param_specs_megatron_row_shards_contraction():
    from repro.core.cascade import CascadeConfig
    from repro.distributed import sharding as shd
    from repro.models import registry
    cfg, model = registry.load("qwen2.5-32b", smoke=True)
    ccfg = CascadeConfig(mode="train", compute_dtype=jnp.float32)
    pshape = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0), ccfg))
    specs = shd.param_specs(pshape, "megatron")
    wo = specs["layers"]["attn"]["wo"]["w"]
    assert wo[-2] == "model" and wo[-1] is None  # row-parallel


# ---------------------------------------------------------------------------
# serve engine + elastic
# ---------------------------------------------------------------------------

def _tiny_engine(max_batch=2, n=None):
    from repro.core.cascade import CascadeConfig
    from repro.models import registry
    from repro.serve.engine import ServeConfig, ServeEngine
    cfg, model = registry.load("codeqwen1.5-7b", smoke=True)
    ccfg = CascadeConfig(mode="train", compute_dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0), ccfg)
    eng = ServeEngine(model, params, ccfg,
                      ServeConfig(max_batch=max_batch, max_len=64))
    return cfg, eng


def test_serve_engine_continuous_batching():
    from repro.serve.engine import Request
    cfg, eng = _tiny_engine(max_batch=2)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                    max_new_tokens=4) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    for _ in range(100):
        eng.step()
        if not eng.busy():
            break
    assert all(r.done for r in reqs)
    assert all(len(r.tokens_out) == 4 for r in reqs)


def test_elastic_replica_failure_requeues_and_completes():
    from repro.serve.elastic import ReplicaSet
    from repro.serve.engine import Request
    cfg, e1 = _tiny_engine(max_batch=2)
    _, e2 = _tiny_engine(max_batch=2)
    rs = ReplicaSet([e1, e2])
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                    max_new_tokens=6) for i in range(6)]
    for r in reqs:
        rs.submit(r)
    rs.step()
    rs.kill_replica(0)  # hard failure with work in flight
    rs.drain(max_steps=200)
    # every uid finished somewhere (original or re-queued failover clone)
    done_uids = {r.uid for r in reqs if r.done} | {r.uid for r in rs.requeued if r.done}
    assert done_uids == {r.uid for r in reqs}, done_uids
    assert not rs.health[0].alive and rs.health[1].alive


def test_serve_engine_crest_bist_detects_injected_faults():
    """CREST as POST/BIST inside the serving engine (paper Section 20.6):
    probe waves on the lm_head weight detect injected column defects while
    requests keep flowing."""
    import jax
    from repro.core import crest as crest_mod
    from repro.core.cascade import CascadeConfig
    from repro.models import registry
    from repro.serve.engine import Request, ServeConfig, ServeEngine
    cfg, model = registry.load("codeqwen1.5-7b", smoke=True)
    ccfg = CascadeConfig(mode="train", compute_dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0), ccfg)
    scfg = ServeConfig(max_batch=2, max_len=48, crest_enabled=True, crest_every=1,
                       crest_cfg=crest_mod.CrestConfig(n_spares=8, threshold=2))
    eng = ServeEngine(model, params, ccfg, scfg)
    eng.fault_mask = crest_mod.inject_column_faults(jax.random.PRNGKey(7), cfg.vocab, 3)
    rng = np.random.default_rng(0)
    for i in range(4):
        eng.submit(Request(uid=i, prompt=rng.integers(0, cfg.vocab, 8).astype(np.int32),
                           max_new_tokens=16))
    for _ in range(200):
        eng.step()
        if not eng.busy():
            break
    # the BIST cycle keeps running between traffic bursts (paper: stress
    # testing in idle periods, Section 20.5)
    for _ in range(3 * cfg.vocab // scfg.crest_cfg.n_spares):
        eng._steps += 1
        eng._crest_probe()
    rep = eng.crest_report()
    assert rep["confirmed_faults"] >= 3, rep
    assert rep["repaired"] >= 3, rep


def test_moe_ep_shardmap_matches_jit_dispatch_single_device():
    """The shard_map expert-parallel MoE must equal the jit capacity
    dispatch on a degenerate (1,1) mesh (plumbing + math identity)."""
    import dataclasses
    import jax
    from repro.core.cascade import CascadeConfig
    from repro.models import registry
    from repro.models.moe import moe_ffn_apply, moe_ffn_init
    from repro.models.moe_shardmap import moe_ffn_apply_ep

    cfg, _ = registry.load("olmoe-1b-7b", smoke=True)
    cfg = dataclasses.replace(cfg, moe_capacity_factor=50.0)  # no drops
    ccfg = CascadeConfig(mode="train", compute_dtype=jnp.float32)
    params = moe_ffn_init(jax.random.PRNGKey(0), cfg, ccfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    y_jit = moe_ffn_apply(params, x, cfg, ccfg)
    y_ep = moe_ffn_apply_ep(params, x, cfg, ccfg, mesh)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_jit),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_moe_ep_shardmap_matches_jit_multirank_subprocess():
    """EP correctness with real expert sharding: 8 virtual devices,
    mesh (2, 4): tokens over 2 data shards, experts over 4 model ranks."""
    import subprocess, sys, os
    code = '''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.core.cascade import CascadeConfig
from repro.models import registry
from repro.models.moe import moe_ffn_apply, moe_ffn_init
from repro.models.moe_shardmap import moe_ffn_apply_ep
cfg, _ = registry.load("olmoe-1b-7b", smoke=True)
cfg = dataclasses.replace(cfg, moe_capacity_factor=50.0)
ccfg = CascadeConfig(mode="train", compute_dtype=jnp.float32)
params = moe_ffn_init(jax.random.PRNGKey(0), cfg, ccfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
y_jit = moe_ffn_apply(params, x, cfg, ccfg)
with mesh:
    y_ep = moe_ffn_apply_ep(params, x, cfg, ccfg, mesh)
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_jit), atol=1e-4, rtol=1e-4)
print("EP-MULTIRANK-OK")
'''
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], cwd=repo,
                          env={**os.environ, "PYTHONPATH": os.path.join(repo, "src")},
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0 and "EP-MULTIRANK-OK" in proc.stdout, \
        proc.stdout[-500:] + proc.stderr[-500:]
