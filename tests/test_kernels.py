"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.kernels import ops

jax.config.update("jax_platform_name", "cpu")


@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (16, 32, 16, 16, 16, 32),
    (48, 64, 32, 16, 16, 32),      # m padded to block
    (128, 256, 128, 64, 64, 64),   # multi-block all dims
    (8, 128, 64, 8, 32, 32),       # K-grid accumulation
    (16, 640, 256, 16, 128, 128),  # K/2 = 320: no multiple of 128 divides it
    # the blocks the call's shapes choose (None: the default caps): the
    # decode grid's M and the extend chunk's, each one M block
    (16, 512, 384, None, None, None),
    (256, 512, 384, None, None, None),
])
@pytest.mark.parametrize("xdtype", [jnp.float32, jnp.bfloat16])
def test_cascade_matmul_vs_ref(m, k, n, bm, bn, bk, xdtype):
    key = jax.random.PRNGKey(m * 1000 + k + n)
    w = jax.random.normal(key, (k, n)) * 0.1
    packed, scales = quant.quantize_weight(w, group_size=bk or 0)
    x = (jax.random.normal(jax.random.PRNGKey(1), (m, k)) * 0.5).astype(xdtype)
    bias = jax.random.normal(jax.random.PRNGKey(2), (n,))
    blocks = {} if bm is None else dict(block_m=bm, block_n=bn, block_k=bk)
    out_k = ops.cascade_matmul(x, packed, scales, bias, **blocks,
                               interpret=True, exact_dequant=False)
    out_r = ops.cascade_matmul_ref(x, packed, scales, bias)
    # the kernel feeds the MXU in bf16 BY DESIGN (TPU path); XLA-CPU's bf16
    # dot is nondeterministically exact-or-rounded, so tolerances are bf16-scale
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=3e-2, rtol=3e-2)


def test_cascade_matmul_batched_leading_dims():
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (64, 32)) * 0.1
    packed, scales = quant.quantize_weight(w)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 64))
    out = ops.cascade_matmul(x, packed, scales, block_m=8, block_n=32, block_k=64,
                             interpret=True, exact_dequant=False)
    ref = ops.cascade_matmul_ref(x.reshape(-1, 64), packed, scales).reshape(2, 5, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("dim,cap,want", [
    (13440, 512, (13440, 384)),    # codeqwen d_ff as K: 105 * 128
    (13440, 256, (13440, 128)),    # ... as N
    (92416, 256, (92416, 256)),
    (4096, 512, (4096, 512)),
    (200, 128, (256, 128)),        # unaligned: padded, never blocked whole
    (64, 256, (64, 64)),           # fits one block: the whole (small) dim
    (48, 16, (48, 16)),
])
def test_fit_block_is_aligned_and_bounded(dim, cap, want):
    assert ops.fit_block(dim, cap) == want


@pytest.mark.parametrize("m,k,n", [(5, 300, 200), (24, 640, 384)])
def test_cascade_matmul_pads_unaligned_dims(m, k, n):
    """K and N that no aligned block divides are zero-padded (per-column
    scales), not blocked whole; the padding contributes nothing."""
    w = jax.random.normal(jax.random.PRNGKey(k), (k, n)) * 0.1
    packed, scales = quant.quantize_weight(w)
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k)) * 0.5
    bias = jax.random.normal(jax.random.PRNGKey(2), (n,))
    out = ops.cascade_matmul(x, packed, scales, bias, block_n=128, block_k=128,
                             interpret=True, exact_dequant=False)
    ref = ops.cascade_matmul_ref(x, packed, scales, bias)
    assert out.shape == (m, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("k,n,group,blocks", [
    (128, 32, 32, dict(block_m=16, block_n=32, block_k=32)),
    # the default caps: the K block is the whole group, one per grid step
    (512, 256, 128, {}),
    (4096, 128, 1024, {}),
])
def test_cascade_matmul_groupwise_scales(k, n, group, blocks):
    w = jax.random.normal(jax.random.PRNGKey(3), (k, n)) * 0.1
    packed, scales = quant.quantize_weight(w, group_size=group)
    assert scales.shape == (k // group, n)
    x = jax.random.normal(jax.random.PRNGKey(4), (16, k)) * 0.5
    out = ops.cascade_matmul(x, packed, scales, **blocks,
                             interpret=True, exact_dequant=False)
    ref = ops.cascade_matmul_ref(x, packed, scales)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("row", [6, 7], ids=["even_k_row", "odd_k_row"])
def test_cascade_matmul_decodes_every_code(row):
    """Each of the 16 FP4 codes (±0 included) in one column, in an even or
    an odd K row (the low or the high nibble of its byte): a one-hot
    activation on that row makes the output the decoded weight times its
    column's scale, exactly."""
    k, n = 32, 128
    codes = np.zeros((k, n), np.uint8)
    codes[row, :16] = np.arange(16)
    codes[row, 16:] = np.random.default_rng(row).integers(0, 16, n - 16)
    packed = quant.pack_fp4(jnp.asarray(codes), axis=0)
    scales = jnp.asarray(2.0 ** np.arange(-3, n - 3) % 7.0 + 0.25, jnp.float32)[None]
    x = jnp.zeros((16, k), jnp.float32).at[:, row].set(1.0)
    out = ops.cascade_matmul(x, packed, scales, interpret=True, exact_dequant=False)
    want = quant.fp4_decode(jnp.asarray(codes[row])) * scales[0]
    np.testing.assert_array_equal(np.asarray(out), np.broadcast_to(want, out.shape))


@pytest.mark.parametrize("b,hq,hkv,s,d,bq,bk", [
    (1, 2, 2, 128, 32, 64, 64),
    (2, 4, 2, 256, 64, 64, 128),   # GQA group=2
    (1, 8, 1, 128, 64, 128, 32),   # MQA
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_vs_ref(b, hq, hkv, s, d, bq, bk, causal):
    keys = jax.random.split(jax.random.PRNGKey(b * 7 + s), 3)
    q = jax.random.normal(keys[0], (b, hq, s, d), jnp.float32)
    k = jax.random.normal(keys[1], (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(keys[2], (b, hkv, s, d), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True)
    ref = ops.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(keys[0], (1, 4, 128, 64), jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, 4, 128, 64), jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, 4, 128, 64), jnp.bfloat16)
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    ref = ops.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref, dtype=np.float32), atol=3e-2, rtol=3e-2)


def test_ssd_scan_ref_matches_chunked_model_impl():
    """The sequential SSD oracle must match the chunked dual form used by the
    Mamba-2 model (arXiv:2405.21060 establishes their equivalence)."""
    from repro.models.ssm import ssd_chunked
    b, s, h, p, g, n = 2, 64, 4, 8, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(keys[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(keys[2], (h,)) * 0.3)
    B = jax.random.normal(keys[3], (b, s, g, n)) * 0.3
    C = jax.random.normal(keys[4], (b, s, g, n)) * 0.3
    D = jnp.ones((h,))
    y_chunk, _ = ssd_chunked(x, dt, A, B, C, D, chunk=16)
    y_ref = jax.vmap(lambda xx, dd, bb, cc: ops.ssd_scan_ref(xx, dd, A, bb, cc, D))(x, dt, B, C)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_ref), atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("bh,s,p,n,chunk", [(2, 64, 8, 4, 16), (4, 128, 16, 8, 32),
                                            (1, 32, 32, 16, 32)])
def test_ssd_scan_kernel_vs_ref(bh, s, p, n, chunk):
    keys = jax.random.split(jax.random.PRNGKey(bh * 31 + s), 5)
    x = jax.random.normal(keys[0], (bh, s, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (bh, s)))
    A = -jnp.exp(jax.random.normal(keys[2], (bh,)) * 0.3)
    B = jax.random.normal(keys[3], (bh, s, n)) * 0.3
    C = jax.random.normal(keys[4], (bh, s, n)) * 0.3
    D = jnp.ones((bh,))
    out = ops.ssd_scan(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    refout = jax.vmap(lambda xx, dd, aa, bb, cc, ddk: ops.ssd_scan_ref(
        xx[:, None, :], dd[:, None], aa[None], bb[:, None, :], cc[:, None, :],
        ddk[None])[:, 0, :])(x, dt, A, B, C, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(refout), atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# exact interpret-mode paths: the fused serving contract is BIT-parity with
# the jnp serve path, not allclose
# ---------------------------------------------------------------------------

def _jnp_serve_matmul(x, packed, scales, bias, out_dtype=jnp.float32):
    """Replicates cascade.linear_apply's serve_fp4 XLA branch (the oracle
    the exact kernel must match bit-for-bit)."""
    w = quant.dequantize_weight(packed, scales, out_dtype)
    if w.shape[0] == x.shape[-1] + 1:   # odd-K pad-to-pack zero row
        x = jnp.pad(x, ((0, 0), (0, 1)))
    out = jnp.dot(x.astype(out_dtype), w, preferred_element_type=jnp.float32)
    if bias is not None:
        out = out + bias
    return out.astype(out_dtype)


@pytest.mark.parametrize("m,k,n,group,use_bias", [
    (4, 256, 512, 0, True),
    (7, 96, 130, 0, False),
    (12, 128, 64, 32, True),    # grouped scales
    (3, 255, 66, 0, True),      # odd K: quantize_weight pad-to-pack
])
def test_cascade_matmul_exact_bit_parity(m, k, n, group, use_bias):
    key = jax.random.PRNGKey(m * 131 + k)
    w = jax.random.normal(key, (k, n)) * 0.1
    packed, scales = quant.quantize_weight(w, group_size=group)
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k)) * 0.5
    bias = jax.random.normal(jax.random.PRNGKey(2), (n,)) if use_bias else None
    out = ops.cascade_matmul(x, packed, scales, bias, interpret=True)
    ref = jax.jit(lambda *a: _jnp_serve_matmul(*a, bias))(x, packed, scales)
    assert bool(jnp.all(out == ref)), float(jnp.max(jnp.abs(out - ref)))


def test_cascade_matmul_exact_requires_interpret():
    w = jax.random.normal(jax.random.PRNGKey(0), (32, 16)) * 0.1
    packed, scales = quant.quantize_weight(w)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
    with pytest.raises(AssertionError):
        ops.cascade_matmul(x, packed, scales, interpret=False,
                           exact_dequant=True)


def _decode_attn_inputs(b, hq, hkv, t, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, hkv, d), jnp.float32)
    valid = (jax.random.uniform(ks[3], (b, t)) > 0.3).astype(jnp.int32)
    return q, k, v, valid.at[:, 0].set(1)   # >= 1 live slot per row


@pytest.mark.parametrize("b,hq,hkv,t,d", [
    (3, 8, 2, 17, 32),    # GQA, ragged T
    (1, 6, 3, 5, 8),
    (2, 4, 4, 64, 16),    # MHA (group=1) — the einsum-lowering trap shape
    (1, 2, 1, 1, 4),      # single cache slot
])
def test_decode_attention_exact_bit_parity(b, hq, hkv, t, d):
    q, k, v, valid = _decode_attn_inputs(b, hq, hkv, t, d, seed=b * 7 + t)
    out = ops.decode_attention(q, k, v, valid)          # interpret => exact
    ref = jax.jit(ops.decode_attention_ref)(q, k, v, valid)
    assert bool(jnp.all(out == ref)), float(jnp.max(jnp.abs(out - ref)))


@pytest.mark.parametrize("b,hq,hkv,t,d,bt", [
    (3, 8, 2, 100, 32, 32),   # T padded to block multiple
    (2, 4, 4, 64, 16, 16),
    (1, 6, 3, 7, 8, 4),
])
def test_decode_attention_streaming_vs_ref(b, hq, hkv, t, d, bt):
    from repro.kernels.flash_attention import decode_attention_pallas
    q, k, v, valid = _decode_attn_inputs(b, hq, hkv, t, d, seed=t)
    out = decode_attention_pallas(q, k, v, valid, block_t=bt,
                                  exact=False, interpret=True)
    ref = ops.decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_streaming_fully_masked_block():
    """A trailing cache block with zero live slots must not pollute the
    running softmax denominator (exp(0)=1 guard in the kernel)."""
    from repro.kernels.flash_attention import decode_attention_pallas
    q, k, v, valid = _decode_attn_inputs(2, 4, 2, 64, 16, seed=5)
    valid = valid.at[:, 32:].set(0)        # second 32-block fully dead
    out = decode_attention_pallas(q, k, v, valid, block_t=32,
                                  exact=False, interpret=True)
    ref = ops.decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# SSD decode through the scan kernel (fused serving dispatch)
# ---------------------------------------------------------------------------

def _ssd_decode_inputs(b, h, p, g, n, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (b, 1, h, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (b, 1, h)))
    A = -jnp.exp(jax.random.normal(keys[2], (h,)) * 0.3)
    B = jax.random.normal(keys[3], (b, 1, g, n)) * 0.3
    C = jax.random.normal(keys[4], (b, 1, g, n)) * 0.3
    D = jnp.ones((h,))
    state = jax.random.normal(keys[5], (b, h, p, n)) * 0.2
    return x, dt, A, B, C, D, state


@pytest.mark.parametrize("b,h,p,g,n", [(2, 4, 8, 2, 16), (1, 2, 16, 1, 8),
                                       (3, 6, 4, 3, 4)])
def test_ssd_decode_matches_jitted_step(b, h, p, g, n):
    """ops.ssd_decode (the scan kernel at s = chunk = 1 with carried slot
    states) vs the JITTED jnp decode step — jit vs jit, because XLA
    contracts a*b+c into FMA under jit but not in eager op-by-op dispatch,
    so the eager form is the one with different numerics, not the kernel.
    Agreement is near-machine-epsilon here (XLA's per-shape fusion choices
    keep strict bitwise from being a universal guarantee); the serving
    contract — TOKEN-exact fused-vs-jnp streams — is gated end-to-end in
    tests/test_fused.py."""
    from repro.models.ssm import ssd_decode_step

    x, dt, A, B, C, D, state = _ssd_decode_inputs(b, h, p, g, n, seed=h + n)
    ref_fn = jax.jit(ssd_decode_step)
    for _ in range(4):                       # carry the state a few steps
        y, ns = ops.ssd_decode(x, dt, A, B, C, D, state)
        y_ref, ns_ref = ref_fn(x, dt, A, B, C, D, state)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(ns), np.asarray(ns_ref),
                                   atol=1e-6, rtol=1e-6)
        state = ns


def test_ssd_scan_carry_splits_at_chunk_boundary():
    """ssd_scan_pallas's carry extension: running the second half with the
    first half's returned state is bit-identical to the unsplit run (the
    kernel is sequential over chunks, so a chunk-aligned split changes no
    reduction order), and a zero initial state reproduces the original
    cold-start path exactly."""
    from repro.kernels.ssd_scan import ssd_scan_pallas

    bh, s, p, n, chunk = 3, 64, 8, 4, 16
    keys = jax.random.split(jax.random.PRNGKey(9), 5)
    x = jax.random.normal(keys[0], (bh, s, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (bh, s)))
    A = -jnp.exp(jax.random.normal(keys[2], (bh,)) * 0.3)
    B = jax.random.normal(keys[3], (bh, s, n)) * 0.3
    C = jax.random.normal(keys[4], (bh, s, n)) * 0.3
    D = jnp.ones((bh,))
    y_cold = ssd_scan_pallas(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    zeros = jnp.zeros((bh, p, n), jnp.float32)
    y_z, fin = ssd_scan_pallas(x, dt, A, B, C, D, chunk=chunk, interpret=True,
                               initial_state=zeros, return_final_state=True)
    assert bool(jnp.all(y_z == y_cold))
    half = s // 2
    y1, s1 = ssd_scan_pallas(x[:, :half], dt[:, :half], A, B[:, :half],
                             C[:, :half], D, chunk=chunk, interpret=True,
                             initial_state=zeros, return_final_state=True)
    y2, s2 = ssd_scan_pallas(x[:, half:], dt[:, half:], A, B[:, half:],
                             C[:, half:], D, chunk=chunk, interpret=True,
                             initial_state=s1, return_final_state=True)
    assert bool(jnp.all(jnp.concatenate([y1, y2], axis=1) == y_cold))
    assert bool(jnp.all(s2 == fin))
