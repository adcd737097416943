"""Fused LSDNN inference layer Pallas TPU kernel.

The paper's flagship heterogeneous workload (§5.3) is the HPEC Large Sparse
Deep Neural Network challenge: 1920 layers of Y <- clamp(relu(Y @ W + b)).
On GPU the reference decomposes this into cuSPARSE spmm + bias + relu
launches; the TPU adaptation fuses the whole layer into one blocked-matmul
kernel with the clamped-relu epilogue applied in registers on the final
K-step — one VMEM round-trip per tile instead of three HBM round-trips.

Grid: (T/bm, G/bn, F/bk), K innermost with an f32 accumulator in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["lsdnn_layer"]


def _lsdnn_kernel(y_ref, w_ref, b_ref, o_ref, acc_ref, *, cap: float):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        y_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        z = acc_ref[...] + b_ref[...].astype(jnp.float32)
        o_ref[...] = jnp.clip(z, 0.0, cap).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("cap", "block_m", "block_n",
                                             "block_k", "interpret"))
def lsdnn_layer(y, w, b, cap: float = 32.0, block_m: int = 256,
                block_n: int = 256, block_k: int = 512,
                *, interpret: bool):
    """y: (T, F); w: (F, G); b: (G,) -> clamp(relu(y @ w + b), 0, cap)."""
    T, F = y.shape
    G = w.shape[1]
    block_m = min(block_m, T)
    block_n = min(block_n, G)
    block_k = min(block_k, F)
    assert T % block_m == 0 and G % block_n == 0 and F % block_k == 0
    grid = (T // block_m, G // block_n, F // block_k)
    b2 = b.reshape(1, G)

    return pl.pallas_call(
        functools.partial(_lsdnn_kernel, cap=cap),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda t, g, k: (t, k)),
            pl.BlockSpec((block_k, block_n), lambda t, g, k: (k, g)),
            pl.BlockSpec((1, block_n), lambda t, g, k: (0, g)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda t, g, k: (t, g)),
        out_shape=jax.ShapeDtypeStruct((T, G), y.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(y, w, b2)
