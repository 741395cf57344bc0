"""The benchmark's operation and byte counts against hand counts."""
import json
from pathlib import Path

import pytest

from bench import counts

ROOT = Path(__file__).resolve().parents[2]


def cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


# per layer: q, k, v, o, then gate, up, down; plus the head
HAND = {
    "codeqwen1.5-7b": 32 * (2 * 4096 * 4096 + 2 * 4096 * 512
                             + 3 * 4096 * 13440) + 4096 * 92416,
    "qwen2.5-32b-tp4": 64 * (2 * 5120 * 5120 + 2 * 5120 * 1024
                             + 3 * 5120 * 27648) + 5120 * 152064,
}
# K and V, every layer, bf16: 32 x 2 x 4 x 128 x 2 = 64 KiB; 64 x 2 x 8 x 128 x 2
KV = {"codeqwen1.5-7b": 2 ** 16, "qwen2.5-32b-tp4": 2 ** 18}


@pytest.mark.parametrize("name", sorted(HAND))
def test_matmul_params(name):
    assert counts.matmul_params(cfg(name)) == HAND[name]


@pytest.mark.parametrize("name", sorted(HAND))
def test_kv_bytes_per_token(name):
    assert counts.kv_bytes_per_token(cfg(name)) == KV[name]


@pytest.mark.parametrize("name", sorted(HAND))
def test_decode_step_work(name):
    c = cfg(name)
    n_lin = 7 * c["num_hidden_layers"] + 1
    cols = sum(n for _, n, _ in counts.linears(c))
    bias = 4 * c["num_hidden_layers"] * (c["num_attention_heads"]
                                         + 2 * c["num_key_value_heads"]) * 128
    rows_io = sum(k + n for k, n, _ in counts.linears(c)) * 2 * 8
    w = counts.decode_step_work(c, active=8, positions=3000)
    attn = c["num_hidden_layers"] * 4 * 3000 * c["num_attention_heads"] * 128
    assert w.flops == 2 * 8 * HAND[name] + attn
    assert len(counts.linears(c)) == n_lin
    assert w.bytes == (HAND[name] / 2 + 4 * cols + bias + rows_io
                       + 3000 * KV[name])


def test_prefill_work_codeqwen():
    c = cfg("codeqwen1.5-7b")
    w = counts.prefill_work(c, prompt=600, chunk=256)
    attn = 32 * 4 * 32 * 128 * 600 * 601 / 2
    assert w.flops == 2 * HAND["codeqwen1.5-7b"] * 600 + attn
    weights = HAND["codeqwen1.5-7b"] / 2 + 4 * sum(n for _, n, _ in counts.linears(c))
    assert w.bytes == 3 * weights + 600 * KV["codeqwen1.5-7b"]


def test_roofline_takes_the_larger_bound():
    w = counts.Work(flops=197e12, bytes=819e9 / 2)
    assert w.roofline_s(197e12, 819e9) == 1.0
    assert counts.Work(1.0, 819e9).roofline_s(197e12, 819e9) == 1.0
