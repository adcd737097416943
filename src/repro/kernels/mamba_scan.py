"""Selective-scan (Mamba1 core) Pallas TPU kernel.

The GPU reference implementation fuses the recurrence into a warp-level
scan; the TPU adaptation instead blocks the *channel* dimension over the
grid and keeps the (block_d, N) state resident in VMEM scratch while the
sequence axis streams through the innermost grid dimension chunk by chunk
(TPU grids are sequential, so the carry is exact). Within a chunk the
recurrence runs as a `fori_loop` over time with all (block_d, N) lanes
vectorized — N=16 channels x 128-lane blocks keep the VPU full.

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t      (per channel d)
    y_t = <h_t, C_t> + skipped D*x (applied by the caller)

Inputs: dt, x: (B, S, dI); A: (dI, N); Bc, Cc: (B, S, N).
Outputs: y (B, S, dI) fp32 and final state (B, dI, N) fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mamba_scan"]


def _scan_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, y_ref, hT_ref, h_ref, *,
                 chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[...]                                   # (bd, N) fp32

    def step(t, h):
        dt_t = dt_ref[0, t, :].astype(jnp.float32)   # (bd,)
        x_t = x_ref[0, t, :].astype(jnp.float32)     # (bd,)
        b_t = b_ref[0, t, :].astype(jnp.float32)     # (N,)
        c_t = c_ref[0, t, :].astype(jnp.float32)     # (N,)
        a = jnp.exp(dt_t[:, None] * A)               # (bd, N)
        h = a * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y_ref[0, t, :] = jnp.sum(h * c_t[None, :], axis=1)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])

    @pl.when(ci == nc - 1)
    def _finish():
        hT_ref[0] = h_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("block_d", "chunk", "interpret"))
def mamba_scan(dt, x, Bc, Cc, A, block_d: int = 512, chunk: int = 128,
               *, interpret: bool):
    """Returns (y (B,S,dI) fp32, hT (B,dI,N) fp32)."""
    B, S, dI = x.shape
    N = A.shape[1]
    block_d = min(block_d, dI)
    chunk = min(chunk, S)
    assert dI % block_d == 0 and S % chunk == 0, (dI, S, block_d, chunk)
    grid = (B, dI // block_d, S // chunk)

    y, hT = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((block_d, N), lambda b, d, c: (d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, block_d, N), lambda b, d, c: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, dI), jnp.float32),
            jax.ShapeDtypeStruct((B, dI, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, N), jnp.float32)],
        interpret=interpret,
    )(dt, x, Bc, Cc, A.astype(jnp.float32))
    return y, hT
