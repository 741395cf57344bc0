"""Operations and bytes the algorithm needs, from the model's shapes.

Derived from ``repro.core.flops`` (parameter counts and the 2 * N * tokens
+ attention FLOP rule), kept with the benchmark so that no program change
can move a roofline. They count the work the model needs, never what a
kernel happens to move: padding, inactive slots and replicated copies are
not counted, so a share of a roofline cannot pass 100% because a later
kernel skips waste.

What a model's work is depends on its kind: each count below dispatches to
the family that the configuration's ``model_type`` names
(``bench/families/<model_type>.py``), which reads its shapes from the
configuration file's keys and, for work that depends on what the program
did (routing), from the run it is given. This module keeps what every
family shares: ``Work``, one FP4 linear's work and the byte sizes.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

from bench import cells

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


def linear_work(m: int, k: int, n: int, bias: bool = False) -> Work:
    """One FP4 linear over ``m`` rows: 2*m*k*n FLOPs; its codes and scales
    (and bias) once, the bf16 rows in and out."""
    w = k * n * FP4_BYTES + n * SCALE_BYTES + (n * 4 if bias else 0)
    return Work(2.0 * m * k * n, w + m * (k + n) * BF16)


def _family(cfg: dict):
    return cells.family(cfg["model_type"])


def layer_linears(cfg: dict) -> List[Tuple[str, int, int, bool]]:
    """(name, K, N, has_bias) of every linear of one layer."""
    return _family(cfg).layer_linears(cfg)


def linears(cfg: dict) -> List[Tuple[int, int, bool]]:
    """(K, N, has_bias) of every linear the model runs per token: all
    layers' and the output head."""
    return _family(cfg).linears(cfg)


def matmul_params(cfg: dict) -> int:
    """Weights every token multiplies by (the embedding is a lookup)."""
    return sum(k * n for k, n, _ in linears(cfg))


def kv_bytes_per_token(cfg: dict) -> int:
    """The cache bytes of one position in every layer."""
    return _family(cfg).kv_bytes_per_token(cfg)


def attention_decode_work(cfg: dict, positions: float) -> Work:
    """One decode step's attention in ONE layer, summed over its active
    slots, which attend over ``positions`` valid cache rows in all."""
    return _family(cfg).attention_decode_work(cfg, positions)


def decode_step_work(cfg: dict, active: int, positions: float, run=None) -> Work:
    """One decode step of the whole model for ``active`` slots attending over
    ``positions`` valid cache rows in all (per layer)."""
    return _family(cfg).decode_step_work(cfg, active, positions, run=run)


def prefill_work(cfg: dict, prompt: int, chunk: int, run=None) -> Work:
    """Prefill of one prompt in chunks of ``chunk`` tokens."""
    return _family(cfg).prefill_work(cfg, prompt, chunk, run=run)


def cascade_matmul_work(cfg: dict, active: int, run=None) -> Work:
    """The FP4 matmul kernel's work in one decode step over the active rows
    (logical shapes, M x K x N)."""
    return _family(cfg).cascade_matmul_work(cfg, active, run=run)
