"""Operations and bytes the algorithm needs, from the model's shapes.

Derived from ``repro.core.flops`` (dense-transformer parameter counts and
the 2 * N * tokens + attention FLOP rule), kept here so that no program
change can move a roofline. They count the work the model needs, never what
a kernel happens to move: padding, inactive slots and replicated copies are
not counted, so a share of a roofline cannot pass 100% because a later
kernel skips waste.

Shapes are read from a configuration file's keys (``hidden_size``,
``intermediate_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``vocab_size``, ``num_hidden_layers``).
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

#: bytes of one FP4 weight (two codes a byte) and of one per-column f32 scale
FP4_BYTES = 0.5
SCALE_BYTES = 4
BF16 = 2


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)

    def roofline_s(self, peak_flops: float, peak_bw: float) -> float:
        """The least time the chip(s) could take: the larger of operations
        over peak FLOP/s and bytes over peak bytes/s."""
        return max(self.flops / peak_flops, self.bytes / peak_bw)


def layer_linears(cfg: dict) -> List[Tuple[str, int, int, bool]]:
    """(name, K, N, has_bias) of every linear of one layer."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    bias = bool(cfg.get("attention_bias", False))
    return [("wq", d, h * hd, bias), ("wk", d, hk * hd, bias),
            ("wv", d, hk * hd, bias), ("wo", h * hd, d, False),
            ("w_gate", d, f, False), ("w_up", d, f, False),
            ("w_down", f, d, False)]


def linears(cfg: dict) -> List[Tuple[int, int, bool]]:
    """(K, N, has_bias) of every linear the model runs per token: all
    layers' and the output head."""
    per = [(k, n, b) for _, k, n, b in layer_linears(cfg)]
    return per * cfg["num_hidden_layers"] + [
        (cfg["hidden_size"], cfg["vocab_size"], False)]


def matmul_params(cfg: dict) -> int:
    """Weights every token multiplies by (the embedding is a lookup)."""
    return sum(k * n for k, n, _ in linears(cfg))


def linear_work(m: int, k: int, n: int, bias: bool = False) -> Work:
    """One FP4 linear over ``m`` rows: 2*m*k*n FLOPs; its codes and scales
    (and bias) once, the bf16 rows in and out."""
    w = k * n * FP4_BYTES + n * SCALE_BYTES + (n * 4 if bias else 0)
    return Work(2.0 * m * k * n, w + m * (k + n) * BF16)


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V of one position in every layer, bf16."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * BF16)


def attention_decode_work(cfg: dict, positions: float) -> Work:
    """One decode step's attention in ONE layer, summed over its active
    slots, which attend over ``positions`` valid cache rows in all: q.k and
    p.v (2 * 2 * H * D FLOPs a row) and each valid K/V row read once."""
    h, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return Work(4.0 * positions * h * hd, positions * 2 * hk * hd * BF16)


def decode_step_work(cfg: dict, active: int, positions: float) -> Work:
    """One decode step of the whole model for ``active`` slots attending over
    ``positions`` valid cache rows in all (per layer): every linear's FP4
    codes and scales, the head included, and the valid K/V rows of every
    layer."""
    att = attention_decode_work(cfg, positions)
    layers = cfg["num_hidden_layers"]
    return cascade_matmul_work(cfg, active) + Work(att.flops * layers,
                                                   att.bytes * layers)


def prefill_work(cfg: dict, prompt: int, chunk: int) -> Work:
    """Prefill of one prompt in chunks of ``chunk`` tokens: 2 * N * tokens
    plus causal attention (each token attends to itself and every earlier
    one), weights streamed once per chunk, K/V of every token written."""
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    layers = cfg["num_hidden_layers"]
    chunks = -(-prompt // chunk)
    flops = (2.0 * matmul_params(cfg) * prompt
             + layers * 4.0 * h * hd * prompt * (prompt + 1) / 2)
    weights = sum(k * n * FP4_BYTES + n * SCALE_BYTES for k, n, _ in linears(cfg))
    return Work(flops, chunks * weights + prompt * kv_bytes_per_token(cfg))


def cascade_matmul_work(cfg: dict, active: int) -> Work:
    """The FP4 matmul kernel's work in one decode step: every linear over
    the active rows (logical shapes, M x K x N)."""
    w = Work()
    for k, n, b in linears(cfg):
        w = w + linear_work(active, k, n, b)
    return w
