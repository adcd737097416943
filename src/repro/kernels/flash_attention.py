"""Flash attention Pallas TPU kernel (GQA, causal, online softmax).

TPU adaptation of the blockwise-attention pattern: the grid is
(batch, q_head, q_block, kv_block) with the kv_block axis innermost — TPU
grids execute sequentially, so the (acc, m, l) online-softmax state lives in
VMEM scratch across kv iterations and the output block is written once on
the last kv step. Block shapes default to MXU-aligned (128, head_dim).

Causal handling: kv blocks strictly above the diagonal are PRUNED — the
``pl.when`` guard skips their compute entirely and the k/v index maps clamp
to the last at-or-below-diagonal block so the revisited block window issues
no new fetch (``prune=False`` restores the old mask-to-NEG_INF behaviour;
the two are bit-identical, see ``tests/test_paged_attention.py``). Blocks
straddling the diagonal still mask element-wise.

GQA: q head h reads kv head h // (H // KV) via the k/v BlockSpec index maps
— no KV replication in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30

__all__ = ["flash_attention"]


def _last_kv_block(qi, block_q: int, block_k: int, nk: int):
    """Index of the last kv block holding any position <= the q block's
    maximum position (blocks after it are fully above the diagonal)."""
    return jnp.minimum((qi * block_q + block_q - 1) // block_k, nk - 1)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  block_q: int, block_k: int, causal: bool, prune: bool):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    last = _last_kv_block(qi, block_q, block_k, nk) if causal and prune \
        else nk - 1

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ki <= last)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, hd)
        hd = q.shape[-1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (hd ** -0.5)                           # (bq, bk)
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)

        m_prev = m_ref[...]                            # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                         # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                # (bq, 1)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == last)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)            # fully-masked rows
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "prune", "interpret"))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = 128, block_k: int = 128,
                    prune: bool = True, *, interpret: bool):
    """q: (B,S,H,hd); k,v: (B,T,KV,hd) -> (B,S,H,hd).

    prune=True (causal only) skips kv blocks fully above the diagonal —
    compute AND fetch — instead of masking them; output is bit-identical.
    interpret=True executes the kernel body with the Pallas interpreter
    (CPU-correct); on TPU pass interpret=False for the Mosaic lowering.
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    grid = (B, H, S // block_q, T // block_k)
    nk = T // block_k

    qt = q.transpose(0, 2, 1, 3)                   # (B, H, S, hd)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    if causal and prune:
        # fully-above-diagonal steps re-address the last active block, so
        # the pipelined copy is elided along with the skipped compute
        def kv_map(b, h, qi, ki):
            return b, h // G, jnp.minimum(
                ki, _last_kv_block(qi, block_q, block_k, nk)), 0
    else:
        def kv_map(b, h, qi, ki):
            return b, h // G, ki, 0

    out = pl.pallas_call(
        functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, prune=prune),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd), kv_map),
            pl.BlockSpec((1, 1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max
            pltpu.VMEM((block_q, 1), jnp.float32),    # running denom
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
