"""Paged KV-cache pool for continuous-batching serve.

The decode cache stops being one contiguous ``(L, B, KV, S_max, hd)`` tensor
per call and becomes a *pool* of fixed-size blocks plus per-sequence block
tables — the paged-attention layout. Sequences of different lengths share
the pool, join and leave the running batch at chunk boundaries, and free
their blocks the moment they retire, so KV memory is bounded by the pool
size instead of ``max_batch * max_len``.

K and V are STACKED in one pool array ``(N, L, 2, R, block, W)`` (block id,
layer, K/V, a page of packed head rows — see :func:`init_kv_pool`) rather
than held as two tensors: the decode write path appends a token's K *and*
V with a single scatter launch and the read paths fetch page pairs with a
single gather (previously two separate ``.at[].set`` / gather launches per
layer per token).

Two halves, deliberately separated:

* :class:`BlockPool` — the HOST-side allocator: a free list of block ids
  with ``alloc`` / ``free`` / ``grow_table`` (mid-decode extension of a live
  sequence's allocation — phase 2 of two-phase admission) /
  ``fragmentation`` / ``defragment``, plus the async-decode DEFERRED-FREE
  FENCE (``free_deferred`` / ``release_deferred``): a preempted row's
  blocks may still be written by the compiled chunk in flight at preemption
  time, so they return to the pool only after the engine has synced past
  that chunk. Thread-safe (admission allocates from the pipeline's SERIAL
  admit stage while retirement frees from the complete stage and the decode
  stage grows). Block id 0 is a reserved *sink*: it is never handed out,
  and jit-compiled decode redirects the KV writes of inactive batch rows
  into it, so masked rows can never corrupt a live sequence's blocks.

  Blocks are REFCOUNTED (prefix caching): ``alloc``/``grow_table`` hand a
  block out at refcount 1, ``incref`` pins it for another holder (a second
  request sharing a cached prompt prefix, or the prefix index itself), and
  ``free``/``free_deferred`` DECREMENT — a block only returns to the free
  list (or enters the deferred fence) when its last reference drops.
  ``defragment`` never relocates anything (tables keep pointing at the
  same ids), and a block with live references is by construction not in
  the free list, so shared (refcount > 1) and index-parked blocks are
  neither free nor movable; ``alloc`` can never hand out a block with
  live refs because only the zero-ref transition re-enters the free list.
* pure jit-able helpers (``scatter_prefill_rows`` / ``scatter_token_window``
  / ``gather_pages`` / ``append_kv`` / ``extend_block_tables`` /
  ``set_table_rows`` / ``set_carry_rows``) — the device-side gather/scatter
  through block tables, used by :func:`repro.models.lm.decode_step_paged`,
  :func:`repro.models.lm.prefill_window_paged` (chunked prefill) and the
  engine's compiled chunk program; ``extend_block_tables`` keeps the
  block-table array device-resident across cycles (growth is an in-place
  scatter, not a re-upload). They close over nothing and take/return
  arrays only, so they trace cleanly under ``jax.jit``/``lax.scan``.
  ``gather_pages`` is the *reference oracle* read path: the serve hot path
  reads pages in place via :mod:`repro.kernels.paged_attention` instead of
  materializing a gathered copy.

A layer-pattern model (Mamba-2 beside attention) keeps its second kind of
state here too: ``init_recurrent_state`` gives one ``(conv, h)`` slot per
batch row for its Mamba layers (the pool then holds its attention layers
only), ``set_state_rows`` zeroes or restores rows of it at seating, and
``write_blocks`` puts a checkpointed row's saved pages back into the pool.
"""
from __future__ import annotations

import threading
from typing import List, Optional, Sequence

import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels.paged_attention import kv_pack, unpack_pages

__all__ = ["BlockPool", "init_kv_pool", "scatter_prefill_row",
           "scatter_prefill_rows", "scatter_token_window", "gather_pages",
           "gather_read_attention", "append_kv", "extend_block_tables",
           "set_table_rows", "set_carry_rows", "copy_blocks", "SINK_BLOCK"]

#: Block id 0 is reserved: never allocated, target of masked-row KV writes.
SINK_BLOCK = 0

_NEG_INF = -2.0 ** 30  # matches models.attention / kernels (bf16-safe)


class BlockPool:
    """Free-list allocator over ``num_blocks`` KV blocks of ``block_size``
    token slots each.

    Invariants (exercised by ``tests/test_kvcache.py`` and
    ``tests/test_prefix_cache.py``):

    * ``num_free + allocated == num_blocks - 1`` (the sink is neither;
      each allocated id counts ONCE however many references hold it);
    * a block id is never handed out twice without its refcount dropping
      to zero through ``free``/``free_deferred`` first;
    * ``free`` of an unallocated (or sink) id raises — including a second
      ``free`` after a shared block's LAST reference already dropped;
    * ``alloc`` is all-or-nothing: it returns ``None`` rather than a partial
      allocation when the pool cannot cover the request (the admission
      back-pressure signal).
    """

    def __init__(self, num_blocks: int, block_size: int) -> None:
        if num_blocks < 2:
            raise ValueError("pool needs >= 2 blocks (block 0 is the sink)")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._lock = threading.Lock()
        # LIFO free list: recently freed blocks are re-used first (warm)
        self._free: List[int] = list(range(num_blocks - 1, SINK_BLOCK, -1))
        self._allocated: set = set()
        #: live reference count per allocated block (prefix sharing): the
        #: free paths DECREMENT and only release at zero
        self._refs: dict = {}
        # deferred-free fence (async decode lookahead): blocks whose owner
        # row may still be WRITTEN by an in-flight compiled chunk sit here —
        # still accounted as allocated, invisible to alloc — until the
        # engine advances the fence (see free_deferred / release_deferred)
        self._deferred_young: List[int] = []
        self._deferred_old: List[int] = []
        self._deferred_set: set = set()
        # reservation floor (admit-vs-stalled-row fairness): the engine
        # reserves the unmet block demand of fenced/stalled resident rows;
        # plain alloc (admission) cannot dip below it, while grow calls
        # pass use_reserved=True and drain it oldest-stalled-first
        self._reserved = 0
        self._g_free = self._g_used = self._g_deferred = None
        self._g_shared = None
        self._g_reserved = None

    def set_metrics(self, metrics) -> None:
        """Bind (or unbind with None) a :class:`repro.obs.MetricsRegistry`:
        the pool keeps ``pool.blocks_free`` / ``pool.blocks_used`` /
        ``pool.blocks_deferred`` gauges current at every alloc, free,
        deferred-free and fence advance. Pool mutations are per-block-batch
        (a handful per engine cycle), so three gauge writes are noise."""
        if metrics is None:
            self._g_free = self._g_used = self._g_deferred = None
            self._g_shared = None
            self._g_reserved = None
            return
        self._g_free = metrics.gauge("pool.blocks_free")
        self._g_used = metrics.gauge("pool.blocks_used")
        self._g_deferred = metrics.gauge("pool.blocks_deferred")
        self._g_shared = metrics.gauge("pool.blocks_shared")
        self._g_reserved = metrics.gauge("pool.blocks_reserved")
        with self._lock:
            self._note_locked()

    def _note_locked(self) -> None:
        if self._g_free is not None:
            self._g_free.set(len(self._free))
            self._g_used.set(len(self._allocated))
            self._g_deferred.set(len(self._deferred_young)
                                 + len(self._deferred_old))
            self._g_shared.set(sum(1 for c in self._refs.values() if c > 1))
        if self._g_reserved is not None:
            self._g_reserved.set(self._reserved)

    # ------------------------------------------------------------- accounting
    @property
    def num_free(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def num_allocated(self) -> int:
        with self._lock:
            return len(self._allocated)

    def blocks_for(self, num_tokens: int) -> int:
        """Blocks needed to hold ``num_tokens`` KV entries."""
        return -(-num_tokens // self.block_size)

    def can_alloc(self, n: int, *, use_reserved: bool = False) -> bool:
        with self._lock:
            return n <= self._avail_locked(use_reserved)

    # ------------------------------------------------- stalled-row reservation
    def set_reserved(self, n: int) -> None:
        """Set the reservation floor: ``n`` free blocks are held back from
        plain :meth:`alloc`/:meth:`grow_table` and only reachable with
        ``use_reserved=True``. The engine sets this to the unmet growth
        demand of stalled resident rows (oldest-stalled-first), so fresh
        admissions cannot indefinitely snipe the blocks a fenced-growth
        row is waiting for. The floor is advisory against what is
        CURRENTLY free — it never blocks frees or fence releases, it just
        earmarks them as they arrive."""
        if n < 0:
            raise ValueError("reservation must be >= 0")
        with self._lock:
            self._reserved = n
            self._note_locked()

    @property
    def reserved(self) -> int:
        with self._lock:
            return self._reserved

    @property
    def num_free_unreserved(self) -> int:
        """Free blocks visible to plain (admission) allocation."""
        with self._lock:
            return self._avail_locked(False)

    def _avail_locked(self, use_reserved: bool) -> int:
        if use_reserved:
            return len(self._free)
        return max(0, len(self._free) - self._reserved)

    # ------------------------------------------------------------- alloc/free
    def alloc(self, n: int, *, use_reserved: bool = False
              ) -> Optional[List[int]]:
        """Take ``n`` blocks at refcount 1, or None (and take nothing) if
        fewer are free. Only the zero-ref transition of ``free`` /
        ``release_deferred`` re-enters the free list, so a block with live
        references can never be handed out here. Plain calls respect the
        stalled-row reservation floor (:meth:`set_reserved`); resident-row
        growth passes ``use_reserved=True`` to drain it."""
        if n < 0:
            raise ValueError("alloc of negative block count")
        with self._lock:
            if n > self._avail_locked(use_reserved):
                return None
            ids = [self._free.pop() for _ in range(n)]
            self._allocated.update(ids)
            for b in ids:
                self._refs[b] = 1
            self._note_locked()
            return ids

    def incref(self, ids: Sequence[int]) -> None:
        """Pin blocks for an additional holder (prefix sharing: a second
        request's table pointing at cached prompt blocks, or the prefix
        index parking a completed request's prefix). Deferred blocks are
        un-pinnable — they are already fenced for release."""
        with self._lock:
            for b in ids:
                if b not in self._allocated or b in self._deferred_set:
                    raise ValueError(
                        f"incref of block {b} that is not live "
                        f"(unallocated, deferred, or the sink)")
                self._refs[b] += 1
            self._note_locked()

    def refcount(self, b: int) -> int:
        """Live references on ``b`` (0 when free/deferred) — the engine's
        copy-on-write trigger: a write into a block with refcount > 1 must
        fork it first."""
        with self._lock:
            return self._refs.get(b, 0)

    @property
    def num_shared(self) -> int:
        """Blocks held by more than one reference."""
        with self._lock:
            return sum(1 for c in self._refs.values() if c > 1)

    def free(self, ids: Sequence[int]) -> None:
        """Drop ONE reference per id; a block returns to the free list only
        when its last reference drops (shared prefix blocks survive their
        co-holders' retirements)."""
        with self._lock:
            for b in ids:
                if b not in self._allocated or b in self._deferred_set:
                    raise ValueError(
                        f"free of block {b} that is not allocated "
                        f"(double free, a deferred block, or the sink)")
                self._refs[b] -= 1
                if self._refs[b] == 0:
                    del self._refs[b]
                    self._allocated.discard(b)
                    self._free.append(b)
            self._note_locked()

    # ------------------------------------------------- deferred-free fence
    def free_deferred(self, ids: Sequence[int]) -> None:
        """Queue blocks for return to the pool behind the async-decode
        FENCE. A preempted row may still be written by the chunk program in
        flight at preemption time (and by a chunked-prefill window enqueued
        the same cycle), so its blocks must not be handed back out until
        that device work has provably retired. Deferred blocks stay
        accounted as allocated (the ``num_free + num_allocated`` invariant
        holds) but are invisible to :meth:`alloc` / :meth:`grow_table`
        until TWO :meth:`release_deferred` calls later.

        Like :meth:`free` this drops ONE reference per id: a SHARED block
        (live refs remain — e.g. a preempted row's prefix blocks still
        held by the prefix index or a co-resident row) is merely
        unpinned, never fenced — the surviving holders' tables still read
        it, and nothing in flight can write a shared prefix block (the
        engine forks before any such write)."""
        with self._lock:
            fenced = []
            for b in ids:
                if b not in self._allocated or b in self._deferred_set:
                    raise ValueError(
                        f"deferred free of block {b} that is not allocated "
                        f"(double free, or the reserved sink)")
                self._refs[b] -= 1
                if self._refs[b] == 0:
                    del self._refs[b]
                    self._deferred_set.add(b)
                    fenced.append(b)
            self._deferred_young.extend(fenced)
            self._note_locked()

    def release_deferred(self) -> int:
        """Advance the fence by one chunk sync: blocks deferred before the
        PREVIOUS advance return to the free list; blocks deferred since then
        age one stage. The engine calls this each time it has synced a
        compiled chunk (every device write enqueued when the blocks were
        deferred precedes the NEXT chunk on the pool's data-dependency
        chain, so two syncs bound all of them). Returns the number of
        blocks released."""
        with self._lock:
            old = self._deferred_old
            self._deferred_old = self._deferred_young
            self._deferred_young = []
            for b in old:
                self._deferred_set.discard(b)
                self._allocated.discard(b)
                self._free.append(b)
            if old:
                self._note_locked()
            return len(old)

    @property
    def num_deferred(self) -> int:
        """Blocks parked behind the deferred-free fence."""
        with self._lock:
            return len(self._deferred_young) + len(self._deferred_old)

    def grow_table(self, blocks: List[int], n: int, *,
                   use_reserved: bool = False) -> Optional[List[int]]:
        """Extend a sequence's existing allocation by ``n`` blocks — the
        mid-decode growth primitive of two-phase admission. All-or-nothing
        like :meth:`alloc`: returns the new ids (also appended to ``blocks``
        in place, keeping the caller's table mirror authoritative) or None
        (taking nothing) when the pool cannot cover the growth — the
        engine's preemption signal. Resident rows grow with
        ``use_reserved=True`` so the stalled-row reservation floor is
        theirs to drain."""
        ids = self.alloc(n, use_reserved=use_reserved)
        if ids is None:
            return None
        blocks.extend(ids)
        return ids

    # ---------------------------------------------------------- fragmentation
    def fragmentation(self) -> float:
        """1 - (longest contiguous free run / free blocks): 0.0 when the
        free ids form one contiguous range, approaching 1.0 as the free set
        shatters. Only genuinely FREE blocks count: deferred (fenced) and
        referenced/parked blocks are excluded — they are neither free nor
        movable. Paged attention reads through the table so this is a
        locality metric, not a correctness one."""
        with self._lock:
            free = sorted(self._free)
        if not free:
            return 0.0
        longest = run = 1
        for a, b in zip(free, free[1:]):
            run = run + 1 if b == a + 1 else 1
            longest = max(longest, run)
        return 1.0 - longest / len(free)

    def defragment(self) -> float:
        """Order the free list so future allocations hand out ascending,
        contiguous-when-possible id runs; returns the fragmentation metric
        after the compaction. Safe while sequences run: allocated blocks are
        never moved (tables keep pointing at the same ids), and blocks with
        live references — shared prefixes, index-parked blocks — or sitting
        behind the deferred-free fence are by invariant not in the free
        list, so the sort cannot disturb them (guarded below: a violation
        means a refcount bug upstream, better loud than silent)."""
        with self._lock:
            bad = [b for b in self._free
                   if b in self._refs or b in self._deferred_set
                   or b == SINK_BLOCK]
            if bad:
                raise RuntimeError(
                    f"free list holds live/deferred/sink blocks {bad}: "
                    "refcount accounting is corrupt")
            self._free.sort(reverse=True)  # LIFO pop() yields ascending ids
        return self.fragmentation()


# ---------------------------------------------------------------- device side
def init_kv_pool(cfg: ModelConfig, num_blocks: int, block_size: int,
                 tp: int = 1) -> jnp.ndarray:
    """Allocate the pooled KV storage: one ``(num_blocks, L, 2, R, block,
    W)`` array — block id first, then layer, K (0) / V (1), and a page of
    ``block`` tokens whose rows pack ``P`` KV heads side by side into
    ``W = P * hd`` lanes (``R = KV / P`` rows; see
    :mod:`repro.kernels.paged_attention`). ``tp`` is the tensor-parallel
    degree the pool is sharded over (on the ``R`` axis): packing is chosen
    from the per-shard KV-head count so no row straddles two shards.

    Why this layout: a page tile ``(block, W)`` fills the chip's 128 lanes
    (one head per row would pad ``hd=64`` to 128, doubling the pool's HBM
    footprint and the bytes every decode step reads), and with the block
    id leading, every write is an update of whole trailing slabs — so the
    decode chunk and prefill window programs update the pool in place
    without relayout copies."""
    if cfg.ssm or cfg.hybrid_attn_every:
        raise ValueError(
            f"{cfg.name}: paged KV applies to attention caches only "
            "(SSM state is O(1) per sequence)")
    # lazy: keeps this module import-light (attention.py imports the
    # gather/scatter helpers above, so a models import here would cycle)
    from ..models.layers import dtype_of
    cdt = dtype_of(cfg.compute_dtype)
    P = kv_pack(cfg.num_kv_heads // tp, cfg.hd)
    shape = (num_blocks, cfg.attn_layers, 2, cfg.num_kv_heads // P,
             block_size, P * cfg.hd)
    return jnp.zeros(shape, cdt)


def init_recurrent_state(cfg: ModelConfig, batch: int):
    """The per-row recurrent state of a layer pattern's Mamba layers, kept
    beside the paged pool by the same engine: ``(conv (Lm, B, K-1,
    conv_dim)`` in the compute dtype, ``h (Lm, B, heads, head_dim, N)``
    f32), one slot a batch row, zero for a fresh sequence — the layout of
    :func:`repro.models.lm.init_cache`'s ``"ssm"``. Its bytes are fixed by
    ``max_batch``, whatever the sequences' lengths (80 MiB a row at
    nemotron-h's five Mamba layers of 256 x 64 x 256)."""
    from ..models.lm import init_cache
    return init_cache(cfg, batch, 0)["ssm"]


def set_state_rows(state, rows: jnp.ndarray, vals=None):
    """Seat rows of the recurrent state: ``state`` ``(conv, h)`` as
    :func:`init_recurrent_state`; rows: (M,) int32 (pad with repeats —
    duplicate writes are idempotent); ``vals`` ``(conv (Lm, M, ...), h
    (Lm, M, ...))`` to restore, or None to zero the rows (a new
    admission: nothing of the slot's last tenant may leak)."""
    if vals is None:
        return tuple(s.at[:, rows].set(0) for s in state)
    return tuple(s.at[:, rows].set(v.astype(s.dtype))
                 for s, v in zip(state, vals))


def write_blocks(pool: jnp.ndarray, ids: jnp.ndarray, pages: jnp.ndarray
                 ) -> jnp.ndarray:
    """Write saved pages back into the pool: ``pool[ids[i]] = pages[i]``
    (a checkpoint-preempted row resumed on fresh blocks; pad ``ids`` with
    the sink). pool: (N, L, 2, R, bs, W); ids: (M,); pages: (M, L, 2, R,
    bs, W)."""
    return pool.at[ids].set(pages.astype(pool.dtype))


def _pack(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """(..., KV, hd) per-head vectors -> (..., KV*hd/width, width) packed
    page rows (heads are contiguous, so packing is a reshape)."""
    return x.reshape(x.shape[:-2] + (-1, width))


def scatter_prefill_row(pool: jnp.ndarray, blocks: jnp.ndarray,
                        krow: jnp.ndarray, vrow: jnp.ndarray) -> jnp.ndarray:
    """Write one prefilled sequence into its blocks.

    pool: (N, L, 2, R, bs, W); blocks: (nb,) int32; krow/vrow:
    (L, KV, S, hd) with ``S <= nb * bs``. Returns the updated pool.
    Jit-safe: ``nb`` and ``S`` are static shapes.
    """
    return scatter_prefill_rows(pool, blocks[None], krow[:, None],
                                vrow[:, None])


def scatter_prefill_rows(pool: jnp.ndarray, blocks: jnp.ndarray,
                         krows: jnp.ndarray, vrows: jnp.ndarray
                         ) -> jnp.ndarray:
    """Write a whole admitted GROUP's prefilled K and V in one scatter.

    pool: (N, L, 2, R, bs, W); blocks: (Bg, nb) int32 — every row uses the
    same block count (the group shares one prompt length, and ``nb`` covers
    the PROMPT footprint only, so the compiled shape keys on the admission
    bucket, not on per-request ``max_new``); krows/vrows: (L, Bg, KV, S, hd)
    with ``S <= nb * bs``. Rows own disjoint blocks, so the scatter indices
    never collide.
    """
    _, L, _, R, bs, W = pool.shape
    Bg, nb = blocks.shape
    _, _, KV, S, hd = krows.shape
    P = W // hd
    rows = jnp.stack([krows, vrows], axis=1)     # (L, 2, Bg, KV, S, hd)
    pad = nb * bs - S
    if pad:
        rows = jnp.pad(rows, ((0, 0), (0, 0), (0, 0), (0, 0), (0, pad),
                              (0, 0)))
    # (L, 2, Bg, R, P, nb, bs, hd) -> (Bg, nb, L, 2, R, bs, P, hd): pages
    paged = rows.reshape(L, 2, Bg, R, P, nb, bs, hd).transpose(
        2, 5, 0, 1, 3, 6, 4, 7).reshape(Bg, nb, L, 2, R, bs, W)
    return pool.at[blocks].set(paged.astype(pool.dtype))


def scatter_token_window(pool: jnp.ndarray, layer, new_k: jnp.ndarray,
                         new_v: jnp.ndarray, tables: jnp.ndarray,
                         start: jnp.ndarray, valid: jnp.ndarray
                         ) -> jnp.ndarray:
    """Write a WINDOW of ``C`` consecutive tokens per batch row through the
    block tables — the chunked-prefill scatter (one launch per layer per
    window, however many rows are mid-prefill).

    pool: (N, L, 2, R, bs, W) the whole pool, updated in place at ``layer``
    (int32 scalar); new_k/new_v: (B, C, KV, hd); tables: (B, max_blocks)
    int32; start: (B,) int32 first write position per row (token ``c``
    lands at ``start[b] + c``); valid: (B, C) bool — invalid entries (rows
    not prefilling, window tail past the prompt) are not written.

    Writes whole pages: each of the ``ceil(C/bs) + 1`` pages a row's window
    can touch is read, merged with the window's tokens, and written back —
    a page holding no valid token goes to the sink block. Valid tokens of
    different rows live in disjoint blocks, so live writes never collide.
    """
    _, _, _, R, bs, W = pool.shape
    B, mb = tables.shape
    C = new_k.shape[1]
    nw = -(-C // bs) + 1                         # pages one window spans
    col = start[:, None] // bs + jnp.arange(nw, dtype=jnp.int32)  # (B, nw)
    p = col[:, :, None] * bs + jnp.arange(bs, dtype=jnp.int32)    # positions
    c = p - start[:, None, None]                 # window column per slot
    cc = jnp.clip(c, 0, C - 1)
    hit = (c >= 0) & (c < C) & jnp.take_along_axis(
        valid, cc.reshape(B, -1), axis=1).reshape(B, nw, bs) \
        & (col < mb)[:, :, None]                 # (B, nw, bs)
    blk = jnp.where(jnp.any(hit, axis=-1), jnp.take_along_axis(
        tables, jnp.clip(col, 0, mb - 1), axis=1), SINK_BLOCK)
    new = _pack(jnp.stack([new_k, new_v], axis=2), W)    # (B, C, 2, R, W)
    tok = jnp.take_along_axis(new, cc.reshape(B, -1, 1, 1, 1), axis=1)
    tok = tok.reshape(B, nw, bs, 2, R, W).transpose(0, 1, 3, 4, 2, 5)
    old = pool[blk, layer]                       # (B, nw, 2, R, bs, W)
    pages = jnp.where(hit[:, :, None, None, :, None],
                      tok.astype(pool.dtype), old)
    return pool.at[blk, layer].set(pages)


def extend_block_tables(tables: jnp.ndarray, rows: jnp.ndarray,
                        cols: jnp.ndarray, blocks: jnp.ndarray
                        ) -> jnp.ndarray:
    """Device-side per-row table-extension scatter: write newly granted
    block ids into the resident block-table array at ``(rows[i], cols[i])``.
    The engine keeps the table array device-resident across cycles; growth
    updates it in place (one tiny scatter) instead of re-uploading the whole
    ``(B, max_blocks)`` table every time a row crosses a block boundary.

    tables: (B, max_blocks) int32; rows/cols/blocks: (M,) int32.
    """
    return tables.at[rows, cols].set(blocks)


def set_table_rows(tables: jnp.ndarray, rows: jnp.ndarray,
                   new_rows: jnp.ndarray) -> jnp.ndarray:
    """Replace whole block-table rows (admission merge writes a sequence's
    prompt blocks; retirement/preemption zeroes the row so the length-bound
    page loops stop advertising it). tables: (B, mb); rows: (M,) int32;
    new_rows: (M, mb) int32."""
    return tables.at[rows].set(new_rows)


def set_carry_rows(lengths: jnp.ndarray, last: jnp.ndarray, rem: jnp.ndarray,
                   rows: jnp.ndarray, new_lengths: jnp.ndarray,
                   new_last: jnp.ndarray, new_rem: jnp.ndarray):
    """Scatter per-row values into the DEVICE-RESIDENT decode carry
    ``(lengths, last, rem)`` — the async-lookahead counterpart of
    :func:`set_table_rows` for the carry arrays. Admission merge seats new
    rows, chunked-prefill completion flips a row into decode, and
    retirement/preemption zeroes a row, all without re-uploading the whole
    mirrors (chunk N+1 consumes chunk N's carry plus these scatters
    directly, so the device dependency chain never waits on the host).

    lengths/last/rem: (B,) int32; rows: (M,) int32 (pad with repeats —
    duplicate writes of the same row are idempotent, keeping the compiled
    shape fixed); new_lengths/new_last/new_rem: (M,) int32.
    """
    return (lengths.at[rows].set(new_lengths),
            last.at[rows].set(new_last),
            rem.at[rows].set(new_rem))


def copy_blocks(pool: jnp.ndarray, srcs: jnp.ndarray, dsts: jnp.ndarray
                ) -> jnp.ndarray:
    """Copy whole KV blocks ``srcs[i] -> dsts[i]`` across every layer in ONE
    gather+scatter launch — the copy-on-write FORK primitive of prefix
    caching: before a row's first divergent write into a shared block, the
    engine clones the block and repoints the row's table at the clone, so
    co-holders keep reading the original bits.

    pool: (N, L, 2, R, bs, W); srcs/dsts: (M,) int32. Call sites pad with
    ``SINK_BLOCK -> SINK_BLOCK`` repeats (the sink's contents are garbage by
    contract, and a self-copy is idempotent) to keep compiled shapes fixed.
    """
    return pool.at[dsts].set(pool[srcs])


def gather_pages(pool: jnp.ndarray, layer, tables: jnp.ndarray, hd: int):
    """Gather one layer's K and V pages for a batch of sequences.

    pool: (N, L, 2, R, bs, W); layer: int32 scalar; tables: (B,
    max_blocks) int32 (unused tail entries point at the sink); hd: head
    dim (unpacks the ``W``-lane rows). Returns ``(ks, vs)``, each (B, KV,
    max_blocks * bs, hd) with token position ``j`` at gathered index ``j``
    — the contiguous view the reference attention path reads, masked by
    each row's length. This materializes O(max_blocks) per row regardless
    of its true length: the oracle the gather-free kernels are tested
    against, not the serve hot path.
    """
    B, mb = tables.shape
    bs = pool.shape[4]
    pages = unpack_pages(pool[tables, layer], hd)  # (B, mb, 2, KV, bs, hd)
    KV = pages.shape[3]
    pages = pages.transpose(2, 0, 3, 1, 4, 5).reshape(2, B, KV, mb * bs, hd)
    return pages[0], pages[1]


def gather_read_attention(q: jnp.ndarray, pool: jnp.ndarray, layer,
                          tables: jnp.ndarray, lengths: jnp.ndarray
                          ) -> jnp.ndarray:
    """The reference (oracle) paged read path: gather the fully padded
    span via :func:`gather_pages`, mask by each row's length, softmax.

    q: (B, H, hd) current-token queries; pool: (N, L, 2, R, bs, W); layer:
    int32 scalar; tables: (B, max_blocks) int32; lengths: (B,) int32
    per-row position ``pos`` (key positions ``0..pos`` attend). Returns
    (B, H, hd) in the pool dtype. O(max_blocks) per row regardless of true
    length — the single definition the gather-free kernels are tested and
    benchmarked against (``tests/test_paged_attention.py``,
    ``benchmarks/paged_decode_microbench.py``) and the ``impl="gather"``
    branch of :func:`repro.models.attention.paged_decode_attention`.
    """
    B, H, hd = q.shape
    ks, vs = gather_pages(pool, layer, tables, hd)  # (B, KV, T, hd)
    KV, T = ks.shape[1], ks.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bksh->bkgs", qg, ks,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    kpos = jnp.arange(T, dtype=jnp.int32)
    s = jnp.where((kpos[None, :] <= lengths[:, None])[:, None, None, :],
                  s, _NEG_INF)
    pmax = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - pmax)
    probs = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(vs.dtype)
    out = jnp.einsum("bkgs,bksh->bkgh", probs, vs)
    return out.reshape(B, H, hd)


def append_kv(pool: jnp.ndarray, layer, new_k: jnp.ndarray,
              new_v: jnp.ndarray, tables: jnp.ndarray, pos: jnp.ndarray,
              active: jnp.ndarray) -> jnp.ndarray:
    """Write one decode step's K AND V for every batch row through the
    block table, in place on the whole pool.

    pool: (N, L, 2, R, bs, W); layer: int32 scalar; new_k/new_v: (B, KV,
    hd); tables: (B, max_blocks); pos: (B,) int32 write position per row;
    active: (B,) bool. Each row's current page is read, the token set at
    its offset, and the page written back whole (see
    :func:`scatter_token_window`). Inactive rows are redirected to the
    sink block so they cannot touch live pages.
    """
    bs, W = pool.shape[4], pool.shape[5]
    B, mb = tables.shape
    idx = jnp.clip(pos // bs, 0, mb - 1)
    blk = jnp.where(active, jnp.take_along_axis(
        tables, idx[:, None], axis=1)[:, 0], SINK_BLOCK)
    off = jnp.where(active, pos % bs, 0)
    new = _pack(jnp.stack([new_k, new_v], axis=1), W)   # (B, 2, R, W)
    hit = jnp.arange(bs, dtype=jnp.int32)[None, :] == off[:, None]
    pages = jnp.where(hit[:, None, None, :, None],
                      new[:, :, :, None, :].astype(pool.dtype),
                      pool[blk, layer])            # (B, 2, R, bs, W)
    return pool.at[blk, layer].set(pages)
