"""Paged decode-attention: gather-free reads through the block table.

The continuous-batching engine's hot path is one-token decode against the
paged KV pool. The original read path (``serve.kvcache.gather_pages``)
materialized a contiguous ``(B, KV, max_blocks*block_size, hd)`` copy of
every row's pages per layer per token and attended over the fully padded
span — O(capacity) HBM traffic and FLOPs regardless of how short the rows
actually are. Both implementations here read K/V pages *in place* through
the block table and skip blocks past each row's true length, making
per-row cost proportional to **occupancy** instead of **capacity**:

* :func:`paged_attention` with ``impl="pallas"`` — the TPU kernel. Grid is
  ``(batch, kv_row, kv_block)`` with the kv-block axis innermost; online
  softmax state ``(acc, m, l)`` lives in VMEM scratch across kv iterations
  (same pattern as ``flash_attention.py``). The layer index, block tables
  and per-row lengths are **scalar-prefetched**
  (``pltpu.PrefetchScalarGridSpec``) so the K/V BlockSpec index maps
  resolve ``tables[b, j]`` *before* the body runs — the DMA engine fetches
  pages straight from the pool and no gathered copy ever exists. Blocks
  past ``ceil((pos+1)/block_size)`` are skipped outright: ``pl.when``
  guards the compute, and the index maps clamp to the last active block so
  Mosaic's revisiting-block elision issues no new fetch. Validated in
  interpret mode on CPU against the gather reference
  (``tests/test_paged_attention.py``) and compiled for the chip in
  ``tests/test_tpu_compile.py``.

* ``impl="xla"`` — the same blockwise online-softmax algorithm lowered
  through plain XLA for backends without Mosaic (the CPU): a
  ``lax.fori_loop`` over pages whose trip count is
  ``max(lengths)//block_size + 1`` — a *traced* bound, so short rows in a
  large pool pay for their pages only. Each iteration touches one page
  pair per row; the full padded span is never materialized. This is the
  engine's default read path off-TPU
  (``repro.kernels.ops.default_paged_impl``).

Page format. The pool is one array ``(N, L, 2, R, block, W)``
(``serve.kvcache.init_kv_pool``): block id first, then layer, K/V, and a
page of ``block`` tokens whose rows are ``W = P * hd`` lanes wide, ``P``
KV heads side by side (head ``r * P + a`` sits in lanes ``a*hd:(a+1)*hd``
of row ``r``). Packing heads makes a page tile ``(block, W)`` fill the
chip's 128 lanes for ``hd < 128`` instead of padding every head to 128,
and putting the block id first keeps every write a whole-trailing-slab
update. Both read paths take the WHOLE pool plus a layer index and
address the layer themselves: the layer loop carries the pool and updates
it in place, so no per-layer slice or pool-sized copy is materialized.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30

#: lane width of a TPU vector register: packed page rows fill at most this
LANES = 128

__all__ = ["paged_attention", "kv_pack", "unpack_pages", "LANES"]


def kv_pack(num_kv_heads: int, hd: int) -> int:
    """KV heads packed into one page row: the largest divisor ``P`` of
    ``num_kv_heads`` with ``P * hd <= LANES`` (1 when ``hd >= LANES``).
    ``num_kv_heads`` is the count one device holds (under tensor
    parallelism, the per-shard count), so a packed row never straddles
    two shards."""
    return max(d for d in range(1, num_kv_heads + 1)
               if num_kv_heads % d == 0 and (d == 1 or d * hd <= LANES))


def unpack_pages(pages, hd: int):
    """``(..., R, block, P*hd)`` packed pages -> ``(..., R*P, block, hd)``
    per-head pages (head ``r * P + a`` from lanes ``a*hd:(a+1)*hd``)."""
    *lead, R, bs, W = pages.shape
    P = W // hd
    x = pages.reshape(*lead, R, bs, P, hd)
    x = jnp.moveaxis(x, -2, -3)                  # (..., R, P, bs, hd)
    return x.reshape(*lead, R * P, bs, hd)


def _paged_kernel(layer_ref, tables_ref, lengths_ref, q_ref, k_ref, v_ref,
                  o_ref, acc_ref, m_ref, l_ref, *, block_size: int,
                  scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)
    pos = lengths_ref[b]
    nb = pos // block_size + 1      # active blocks: ceil((pos+1)/block)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j < nb)
    def _body():
        # q rows are block-diagonal over the packed heads: row (a, g)
        # carries query head (r*P + a)*G + g in lanes a*hd:(a+1)*hd and
        # zeros elsewhere, so one (P*G, W) x (W, block) product yields
        # every packed head's scores exactly
        q = q_ref[0, 0].astype(jnp.float32)          # (P*G, W)
        k = k_ref[0, 0, 0, 0].astype(jnp.float32)    # (bs, W)
        v = v_ref[0, 0, 0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                # (P*G, bs)
        kpos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nb - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)              # fully-masked rows
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_attention_pallas(q, pool_kv, tables, lengths, layer,
                            interpret: bool):
    B, H, hd = q.shape
    _, _, _, R, bs, W = pool_kv.shape
    P = W // hd
    G = H // (R * P)
    mb = tables.shape[1]
    # block-diagonal packed queries: (B, R, P*G, W)
    qg = q.reshape(B, R, P, G, 1, hd)
    eye = jnp.eye(P, dtype=bool).reshape(1, 1, P, 1, P, 1)
    qw = jnp.where(eye, qg, jnp.zeros((), q.dtype)).reshape(B, R, P * G, W)

    # scalar-prefetched index maps: the page fetched at grid step (b, r, j)
    # is pool_kv[tables[b, j], layer, 0|1, r]; past-the-length steps clamp
    # to the last active block, so the revisited window needs no new fetch
    def kv_map(half):
        def index_map(b, r, j, layer, tables, lengths):
            jc = jnp.minimum(j, lengths[b] // bs)
            return tables[b, jc], layer[0], half, r, 0, 0
        return index_map

    def q_map(b, r, j, layer, tables, lengths):
        return b, r, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, R, mb),
        in_specs=[
            pl.BlockSpec((1, 1, P * G, W), q_map),
            pl.BlockSpec((1, 1, 1, 1, bs, W), kv_map(0)),
            pl.BlockSpec((1, 1, 1, 1, bs, W), kv_map(1)),
        ],
        out_specs=pl.BlockSpec((1, 1, P * G, W), q_map),
        scratch_shapes=[
            pltpu.VMEM((P * G, W), jnp.float32),   # acc
            pltpu.VMEM((P * G, 1), jnp.float32),   # running max
            pltpu.VMEM((P * G, 1), jnp.float32),   # running denom
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, block_size=bs, scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R, P * G, W), q.dtype),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), tables, lengths, qw,
      pool_kv, pool_kv)
    # keep each packed head's own lane block: (B, R, P, G, hd)
    out = jnp.diagonal(out.reshape(B, R, P, G, P, hd), axis1=2, axis2=4)
    return jnp.moveaxis(out, -1, 2).reshape(B, H, hd)


def _paged_attention_xla(q, pool_kv, tables, lengths, layer):
    """Blockwise online softmax as a traced-bound page loop (see module
    docstring). Decode is inference-only, so the while-loop lowering is
    fine; the loop body is the same math as the Pallas kernel body."""
    B, H, hd = q.shape
    bs = pool_kv.shape[4]
    KV = pool_kv.shape[3] * pool_kv.shape[5] // hd
    G = H // KV
    qg = q.reshape(B, KV, G, hd).astype(jnp.float32)
    nb_row = lengths // bs + 1
    nb_max = jnp.max(nb_row)

    def body(j, carry):
        acc, m, l = carry
        jc = jnp.minimum(j, nb_row - 1)              # clamp per row
        blk = jnp.take_along_axis(tables, jc[:, None], axis=1)[:, 0]
        # one gather straight from the whole pool: (B, 2, KV, bs, hd)
        kv_j = unpack_pages(pool_kv[blk, layer], hd).astype(jnp.float32)
        s = jnp.einsum("bkgh,bksh->bkgs", qg, kv_j[:, 0],
                       preferred_element_type=jnp.float32) * (hd ** -0.5)
        kpos = jc[:, None] * bs + jnp.arange(bs, dtype=jnp.int32)
        # rows whose pages ran out contribute nothing (jc would re-read
        # their LAST page — without the j < nb_row term it double-counts)
        mask = (kpos <= lengths[:, None]) & (j < nb_row)[:, None]
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bkgs,bksh->bkgh", p, kv_j[:, 1])
        return acc, m_new, l

    acc = jnp.zeros((B, KV, G, hd), jnp.float32)
    m = jnp.full((B, KV, G, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((B, KV, G, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, nb_max, body, (acc, m, l))
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l).reshape(B, H, hd).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("impl", "interpret"))
def paged_attention(q, pool_kv, tables, lengths, layer, *, impl: str,
                    interpret: bool):
    """One-token decode attention straight off the paged KV pool.

    q: (B, H, hd) current-token queries (post-RoPE); pool_kv: (N, L, 2, R,
    block, P*hd) packed K/V pages of EVERY layer (see the module docstring);
    tables: (B, max_blocks) int32 block tables (unused tail entries point
    at the sink block); lengths: (B,) int32 per-row position ``pos`` — the
    row attends over key positions ``0..pos`` inclusive, i.e. the entry
    :func:`repro.serve.kvcache.append_kv` just wrote plus everything before
    it; layer: int32 scalar, the layer whose pages are read. Returns
    (B, H, hd).

    impl="pallas" is the Pallas kernel (interpret=True for the CPU-correct
    interpreter, False for Mosaic on TPU; :mod:`repro.kernels.ops` derives
    it from the platform); impl="xla" is the traced-bound page loop and
    ignores ``interpret``. Both skip pages past each row's length.
    """
    if impl == "pallas":
        return _paged_attention_pallas(q, pool_kv, tables, lengths, layer,
                                       interpret=interpret)
    if impl == "xla":
        return _paged_attention_xla(q, pool_kv, tables, lengths, layer)
    raise ValueError(f"unknown paged attention impl {impl!r} "
                     "(expected 'pallas' or 'xla')")
