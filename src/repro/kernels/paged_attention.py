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
  ``(batch,)``: one step per row, whatever the table width, so pages past
  a row's length cost nothing at all. The pool stays in HBM
  (``memory_space=pl.ANY``); the layer index, block tables and per-row
  lengths are **scalar-prefetched** (``pltpu.PrefetchScalarGridSpec``).
  A row's ``ceil((pos+1)/block_size)`` live pages are fetched by manual
  DMA in groups of :func:`pages_per_group` pages, one DMA per page moving
  the page's whole ``(2, R, block, W)`` slab (K and V of every packed
  row), into two VMEM slots: group ``g+1`` streams in while group ``g``
  is computed, and the next row's first group streams in during the
  row's last compute, so the DMA engine never waits on the grid. The
  group size follows the shapes (about ``GROUP_BYTES`` a group). Online
  softmax state ``(acc, m, l)`` lives in VMEM scratch, one set per packed
  KV row; only the last group can hold positions past ``pos`` and is the
  only one masked. Validated in interpret mode on CPU against the gather
  reference (``tests/test_paged_attention.py``) and compiled for the chip
  in ``tests/test_tpu_compile.py``.

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
update and makes one layer's page one contiguous ``(2, R, block, W)``
slab, which the kernel fetches with one DMA. Both read paths take the WHOLE pool plus a layer index and
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


#: bytes one multi-page DMA group aims at: enough to keep HBM busy on one
#: transfer, small enough that both slots sit in scoped VMEM many times over
GROUP_BYTES = 1 << 20


def pages_per_group(R: int, block: int, W: int, itemsize: int,
                    max_blocks: int) -> int:
    """Pages the kernel fetches per group: ``GROUP_BYTES`` of whole pages
    (each page ``(2, R, block, W)``, K and V of every packed row), at least
    one and at most the table width."""
    page = 2 * R * block * W * itemsize
    return max(1, min(max_blocks, GROUP_BYTES // page))


def _paged_kernel(layer_ref, tables_ref, lengths_ref, q_ref, pool_hbm, o_ref,
                  buf, sems, slot0_ref, acc_ref, m_ref, l_ref, *,
                  block_size: int, group: int, scale: float):
    b = pl.program_id(0)
    bs, K = block_size, group
    R = acc_ref.shape[0]
    mb = tables_ref.shape[1]
    layer = layer_ref[0]

    def n_pages(row):               # active pages: ceil((pos+1)/block)
        return jnp.minimum(lengths_ref[row] // bs + 1, mb)

    def group_copies(row, g, slot, op):
        """Start or wait on group ``g`` of ``row`` in ``slot``: one DMA per
        live page, each the page's whole ``(2, R, bs, W)`` slab. Start and
        wait see the same guards, so every started copy is waited on."""
        nb = n_pages(row)
        for i in range(K):
            j = g * K + i

            @pl.when(j < nb)
            def _():
                page = tables_ref[row, j]
                op(pltpu.make_async_copy(pool_hbm.at[page, layer],
                                         buf.at[slot, i], sems.at[slot]))

    def start(row, g, slot):
        group_copies(row, g, slot, lambda c: c.start())

    @pl.when(b == 0)
    def _first():
        slot0_ref[0] = 0
        start(0, 0, 0)

    pos = lengths_ref[b]
    ng = pl.cdiv(n_pages(b), K)
    s0 = slot0_ref[0]               # this row's first group was started there
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def compute(g, slot, last):
        def kv_row(r, carry):
            # q rows are block-diagonal over the packed heads: row (a, e)
            # carries query head (r*P + a)*G + e in lanes a*hd:(a+1)*hd and
            # zeros elsewhere, so one (P*G, W) x (W, K*bs) product yields
            # every packed head's scores exactly
            q = q_ref[0, r].astype(jnp.float32)               # (P*G, W)
            k = buf[slot, :, 0, r].astype(jnp.float32).reshape(K * bs, -1)
            v = buf[slot, :, 1, r].astype(jnp.float32).reshape(K * bs, -1)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale                                     # (P*G, K*bs)
            if last:
                # only the last group holds positions past pos; pages it
                # did not fetch hold stale VMEM, so V is masked as well
                base = g * (K * bs)
                kpos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(kpos <= pos, s, NEG_INF)
                vpos = base + jax.lax.broadcasted_iota(
                    jnp.int32, (K * bs, 1), 0)
                v = jnp.where(vpos <= pos, v, 0.0)
            m_prev = m_ref[r]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[r] = alpha * l_ref[r] + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[r] = acc_ref[r] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[r] = m_new
            return carry

        # unrolled: the rows' small matmuls and softmax steps interleave
        jax.lax.fori_loop(0, R, kv_row, 0, unroll=True)

    def run_group(g, last):
        slot = jax.lax.rem(s0 + g, 2)
        if last:
            # the next row's first group streams in during this compute
            @pl.when(b + 1 < pl.num_programs(0))
            def _():
                start(b + 1, 0, 1 - slot)
                slot0_ref[0] = 1 - slot
        else:
            start(b, g + 1, 1 - slot)
        group_copies(b, g, slot, lambda c: c.wait())
        compute(g, slot, last)

    def full_group(g, carry):
        run_group(g, last=False)
        return carry

    jax.lax.fori_loop(0, ng - 1, full_group, 0)
    run_group(ng - 1, last=True)
    o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _paged_attention_pallas(q, pool_kv, tables, lengths, layer,
                            interpret: bool, group: int | None = None):
    """The kernel (see the module docstring). ``group`` pages a DMA
    group: :func:`pages_per_group` of the shapes unless a test sets it."""
    B, H, hd = q.shape
    _, _, _, R, bs, W = pool_kv.shape
    P = W // hd
    G = H // (R * P)
    mb = tables.shape[1]
    if group is None:
        group = pages_per_group(R, bs, W, pool_kv.dtype.itemsize, mb)
    # block-diagonal packed queries: (B, R, P*G, W)
    qg = q.reshape(B, R, P, G, 1, hd)
    eye = jnp.eye(P, dtype=bool).reshape(1, 1, P, 1, P, 1)
    qw = jnp.where(eye, qg, jnp.zeros((), q.dtype)).reshape(B, R, P * G, W)

    def row_map(b, layer, tables, lengths):
        return b, 0, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, R, P * G, W), row_map),
            pl.BlockSpec(memory_space=pl.ANY),     # the pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, R, P * G, W), row_map),
        scratch_shapes=[
            pltpu.VMEM((2, group, 2, R, bs, W), pool_kv.dtype),  # 2 slots
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),           # slot of next row's group 0
            pltpu.VMEM((R, P * G, W), jnp.float32),   # acc
            pltpu.VMEM((R, P * G, 1), jnp.float32),   # running max
            pltpu.VMEM((R, P * G, 1), jnp.float32),   # running denom
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, block_size=bs, group=group,
                          scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R, P * G, W), q.dtype),
        # the HLO op's name in the device trace, which
        # ``paged_decode_roofline`` matches
        name="paged_attention",
        # rows run in order on one core: each prefetches the next row's
        # first group
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), tables, lengths, qw,
      pool_kv)
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
