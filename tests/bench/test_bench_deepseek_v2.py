"""The DeepSeek-V2 family's counts against hand sums at the published
shapes, with and without a run's routing counters, its router fill rule,
and the reader of the held experts' load."""
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells as C
from bench import counts, phases

ROOT = Path(__file__).resolve().parents[2]
FAM = C.family("deepseek_v2")


def cfg():
    return json.loads((ROOT / "bench/configs/deepseek-v2-ep20.json").read_text())


# the kernel's linears: MLA per layer wq_a 5120x1536, wq_b 1536x(128x192),
# wkv_a 5120x(512+64), wo (128x128)x5120; the dense MLP 3 x 5120x12288; the
# shared experts 3 x 5120x(2x1536); the head 5120x102400
MLA = 5120 * 1536 + 1536 * 24576 + 5120 * 576 + 16384 * 5120
DENSE, SHARED, HEAD = 3 * 5120 * 12288, 3 * 5120 * 3072, 5120 * 102400
KERNEL = 8 * MLA + DENSE + 7 * SHARED + HEAD
#: the latent (512) and the rope key (64) of one position, eight layers, bf16
KV = 8 * 576 * 2


def test_kernel_linears_and_cache_bytes():
    c = cfg()
    assert MLA == 132_448_256
    assert counts.matmul_params(c) == KERNEL == 2_102_919_168
    assert len(counts.linears(c)) == 8 * 4 + 3 + 7 * 3 + 1
    assert counts.kv_bytes_per_token(c) == KV == 9216
    cols = sum(n for _, n, _ in counts.linears(c))
    rows_io = sum(k + n for k, n, _ in counts.linears(c)) * 2 * 128
    w = counts.cascade_matmul_work(c, 128)
    assert w.flops == 2 * 128 * KERNEL
    assert w.bytes == KERNEL / 2 + 4 * cols + rows_io


def test_absorbed_attention_per_row():
    # scores against latent and rope key, 2 x 128 x 576; context, 2 x 128 x 512
    w = counts.attention_decode_work(cfg(), positions=1000)
    assert w.flops == 1000 * (2 * 128 * 576 + 2 * 128 * 512) == 278_528_000
    assert w.bytes == 1000 * 1152


def _decode_hand(assign, pairs, active=128, positions=300_000):
    """One decode step by hand: the kernel, eight layers of absorbed MLA (and
    their absorption products and wkv_b), seven routers, the held experts."""
    k = counts.cascade_matmul_work(cfg(), active)
    wkv_b = 512 * 32768 / 2 + 32768 * 4
    expert = 3 * 5120 * 1536 / 2 + (1536 + 1536 + 5120) * 4
    flops = (k.flops + 8 * positions * 278_528 + 8 * 2 * active * 128 * 512 * 256
             + 7 * 2 * active * 5120 * 160 + assign * 6 * 5120 * 1536)
    nbytes = (k.bytes + 8 * (positions * 1152 + wkv_b)
              + 7 * (5120 * 160 * 4 + active * 5120 * 2)
              + pairs * expert + assign * 3 * (5120 + 1536) * 2)
    return flops, nbytes


def test_decode_step_without_a_run_takes_the_expectation():
    # 128 tokens x 7 layers x 6 picks x 8/160 held; each of the 56 pairs
    # idle with probability (1 - 6/160)^128
    assign = 128 * 7 * 6 * 8 / 160
    pairs = 56 * (1 - (1 - 6 / 160) ** 128)
    assert assign == 268.8 and 55.5 < pairs < 55.6
    w = counts.decode_step_work(cfg(), active=128, positions=300_000)
    flops, nbytes = _decode_hand(assign, pairs)
    assert w.flops == pytest.approx(flops, rel=1e-12)
    assert w.bytes == pytest.approx(nbytes, rel=1e-12)


#: a window's routed-work counter: 10 steps of 128 rows
ROUTED = {"calls": 0, "seconds": 0.0, "steps": 10, "rows": 1280,
          "assignments": 2560, "pairs": 350, "layer_experts": 400}


@pytest.fixture()
def counted(monkeypatch):
    monkeypatch.setattr(phases, "window_counters",
                        lambda run: {"serve.step": {"calls": 10}, "serve.routed": ROUTED})


def test_decode_step_reads_the_runs_routing(counted):
    run = object()
    assert FAM.routed(cfg(), 64, run) == (64 * 2560 / 1280, 35.0)
    w = counts.decode_step_work(cfg(), active=128, positions=300_000, run=run)
    flops, nbytes = _decode_hand(256.0, 35.0)
    assert w.flops == pytest.approx(flops, rel=1e-12)
    assert w.bytes == pytest.approx(nbytes, rel=1e-12)
    # the kernel's share does not depend on routing
    assert counts.cascade_matmul_work(cfg(), 128, run=run) == \
        counts.cascade_matmul_work(cfg(), 128)


def test_a_run_without_the_counter_takes_the_expectation(monkeypatch):
    monkeypatch.setattr(phases, "window_counters", lambda run: {"serve.step": {}})
    assert FAM.routed(cfg(), 128, object()) == FAM.routed(cfg(), 128)
    monkeypatch.setattr(phases, "window_counters", lambda run: None)
    assert FAM.routed(cfg(), 128, object()) == FAM.routed(cfg(), 128)


def test_held_expert_tokens_reads_the_counter(counted):
    assert C.metric_reader("held_expert_tokens")(object()) == 2560 / 400


def test_held_expert_tokens_reads_nothing_without_it(monkeypatch):
    monkeypatch.setattr(phases, "window_counters", lambda run: {"serve.step": {}})
    assert C.metric_reader("held_expert_tokens")(object()) is None


def test_prefill_work():
    c = cfg()
    w = counts.prefill_work(c, prompt=600, chunk=256)
    wkv_b = 512 * 32768
    pairs_flops = 8 * 2 * 128 * (192 + 128) * 600 * 601 / 2
    assign = lambda n: n * 7 * 6 * 8 / 160
    flops = (2 * (KERNEL + 8 * wkv_b) * 600 + pairs_flops + 7 * 2 * 600 * 5120 * 160
             + sum(assign(n) for n in (256, 256, 88)) * 6 * 5120 * 1536)
    assert w.flops == pytest.approx(flops, rel=1e-12)


def test_router_fill_rule():
    sds = jax.ShapeDtypeStruct((5, 5120, 160), jnp.float32)
    r = FAM.fill(["layers", "moe", "router"], sds, jax.random.PRNGKey(0), None)
    assert r.shape == (5, 5120, 160) and r.dtype == jnp.float32
    assert float(jnp.std(r)) == pytest.approx(1 / math.sqrt(5120), rel=0.01)
    assert abs(float(jnp.mean(r))) < 1e-4
    assert FAM.fill(["layers", "attn", "wo", "codes"], sds, jax.random.PRNGKey(0), 16) is None


def test_model_refuses_what_the_program_cannot_run():
    c = cfg()
    for key, value in [("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                       ("yarn_factor", 4), ("n_routed_experts", 161)]:
        with pytest.raises(ValueError):
            FAM.model("x", dict(c, **{key: value}))


def test_yarn_keys_repeat_rope_scaling():
    c = cfg()
    assert {k: c[k] for k in FAM.YARN_KEYS} == {
        k: c["rope_scaling"][v] for k, v in FAM.YARN_KEYS.items()}
    inv, m, scale = FAM.yarn(c)
    assert m == 1.0
    assert scale == pytest.approx(192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2)
    np.testing.assert_allclose(inv[:10], 1e4 ** (-np.arange(0, 20, 2) / 64), rtol=1e-6)
