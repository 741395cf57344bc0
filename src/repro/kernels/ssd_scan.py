"""Mamba-2 SSD scan — Pallas TPU kernel.

Grid (batch*heads, n_chunks): the chunk dimension is sequential ("arbitrary")
and the (P, N) SSM state lives in VMEM scratch across chunk iterations — the
same carry-stays-on-chip dataflow CASCADE uses for partial sums. Within a
chunk the recurrence runs as a fori_loop over the chunk's steps on VMEM
tiles (HBM->VMEM staging via BlockSpec = the HILT analogue).

Inputs are pre-broadcast per head (callers expand B/C groups):
  x:  (BH, S, P)   dt: (BH, S)   A: (BH,)   B, C: (BH, S, N)   D: (BH,)
(the wrapper hands dt to the kernel as (BH, S, 1) and A, D in SMEM).
Output y: (BH, S, P).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_chunk(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, state_ref,
                ys_ref, *, chunk):
    """Advance the VMEM state over this chunk's steps. Every per-step
    operand is read from its ref as a (1, ·) row at a dynamic offset and
    every output row is written to the f32 ``ys_ref`` scratch — Mosaic
    lowers dynamic ref slices, not dynamic slices of loaded values."""
    h = pl.program_id(0)
    a = a_ref[h]                          # per-head scalars live in SMEM
    dskip = d_ref[h]

    def step(i, state):
        row = pl.ds(i, 1)
        x = x_ref[0, row, :].astype(jnp.float32)     # (1, P)
        dt = dt_ref[0, row, :].astype(jnp.float32)   # (1, 1)
        bb = b_ref[0, row, :].astype(jnp.float32)    # (1, N)
        cc = c_ref[0, row, :].astype(jnp.float32)    # (1, N)
        decay = jnp.exp(dt * a)
        state = state * decay + (dt * x).T * bb       # (P, N)
        y = jax.lax.dot_general(cc, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (1, P)
        ys_ref[row, :] = y + dskip * x
        return state

    state_ref[...] = jax.lax.fori_loop(0, chunk, step, state_ref[...])
    y_ref[0] = ys_ref[...].astype(y_ref.dtype)


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, state_ref,
            ys_ref, *, chunk):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    _scan_chunk(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, state_ref,
                ys_ref, chunk=chunk)


def _kernel_carry(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s0_ref,
                  y_ref, out_s_ref, state_ref, ys_ref, *, chunk):
    """The scan kernel with a caller-supplied initial state and the final
    state emitted as a second output — the serving-decode entry point (a
    decode step is this kernel at s = chunk = 1)."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        state_ref[...] = s0_ref[0]

    _scan_chunk(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, state_ref,
                ys_ref, chunk=chunk)
    # same (h, 0, 0) block every chunk: the last sequential write is the one
    # flushed back to HBM, i.e. the post-scan state
    out_s_ref[0] = state_ref[...]


def ssd_scan_pallas(x, dt, A, B, C, D, *, chunk: int = 64,
                    interpret: bool = False, initial_state=None,
                    return_final_state: bool = False):
    bh, s, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    # dt as (BH, S, 1): a (1, chunk, 1) block meets the (8, 128) block rule
    # at chunk = 1 (decode) and any multiple of 8; A and D are whole (BH,)
    # vectors in SMEM, read per head as scalars
    dt = dt.reshape(bh, s, 1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [
        pl.BlockSpec((1, chunk, p), lambda h, c: (h, c, 0)),
        pl.BlockSpec((1, chunk, 1), lambda h, c: (h, c, 0)),
        smem,
        pl.BlockSpec((1, chunk, n), lambda h, c: (h, c, 0)),
        pl.BlockSpec((1, chunk, n), lambda h, c: (h, c, 0)),
        smem,
    ]
    scratch = [pltpu.VMEM((p, n), jnp.float32), pltpu.VMEM((chunk, p), jnp.float32)]
    params = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))
    if initial_state is None and not return_final_state:
        return pl.pallas_call(
            functools.partial(_kernel, chunk=chunk),
            grid=(bh, nc),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, chunk, p), lambda h, c: (h, c, 0)),
            out_shape=jax.ShapeDtypeStruct((bh, s, p), x.dtype),
            scratch_shapes=scratch,
            compiler_params=params,
            interpret=interpret,
        )(x, dt, A, B, C, D)
    s0 = (jnp.zeros((bh, p, n), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))
    y, fin = pl.pallas_call(
        functools.partial(_kernel_carry, chunk=chunk),
        grid=(bh, nc),
        in_specs=in_specs + [pl.BlockSpec((1, p, n), lambda h, c: (h, 0, 0))],
        out_specs=[pl.BlockSpec((1, chunk, p), lambda h, c: (h, c, 0)),
                   pl.BlockSpec((1, p, n), lambda h, c: (h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, s, p), x.dtype),
                   jax.ShapeDtypeStruct((bh, p, n), jnp.float32)],
        scratch_shapes=scratch,
        compiler_params=params,
        interpret=interpret,
    )(x, dt, A, B, C, D, s0)
    return (y, fin) if return_final_state else y
