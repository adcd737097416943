"""Paged KV-cache pool: allocator invariants + gather/scatter correctness."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.serve.kvcache import (SINK_BLOCK, BlockPool, append_kv,
                                 gather_pages, init_kv_pool,
                                 scatter_prefill_row)


# ------------------------------------------------------------- allocator
def test_alloc_free_accounting():
    bp = BlockPool(num_blocks=9, block_size=4)
    assert bp.num_free == 8          # block 0 is the reserved sink
    a = bp.alloc(3)
    b = bp.alloc(2)
    assert SINK_BLOCK not in a + b
    assert len(set(a + b)) == 5      # no id handed out twice
    assert bp.num_free == 3 and bp.num_allocated == 5
    bp.free(a)
    assert bp.num_free == 6 and bp.num_allocated == 2
    c = bp.alloc(6)                  # re-uses the freed ids
    assert len(set(b + c)) == 8
    assert bp.num_free == 0


def test_alloc_is_all_or_nothing():
    bp = BlockPool(num_blocks=5, block_size=4)
    assert bp.alloc(5) is None       # only 4 usable
    assert bp.num_free == 4          # nothing was taken
    got = bp.alloc(4)
    assert got is not None and bp.alloc(1) is None


def test_double_free_and_sink_free_raise():
    bp = BlockPool(num_blocks=4, block_size=2)
    ids = bp.alloc(2)
    bp.free(ids)
    with pytest.raises(ValueError, match="not allocated"):
        bp.free([ids[0]])
    with pytest.raises(ValueError, match="not allocated"):
        bp.free([SINK_BLOCK])


def test_blocks_for_and_fragmentation():
    bp = BlockPool(num_blocks=17, block_size=4)
    assert bp.blocks_for(1) == 1 and bp.blocks_for(4) == 1
    assert bp.blocks_for(5) == 2 and bp.blocks_for(17) == 5
    # carve holes: free every other allocation
    held = [bp.alloc(1) for _ in range(16)]
    for i in range(0, 16, 2):
        bp.free(held[i])
    frag = bp.fragmentation()
    assert frag > 0.5                # free set is maximally shattered
    assert bp.defragment() == pytest.approx(bp.fragmentation())
    # freeing the rest makes the free set contiguous again
    for i in range(1, 16, 2):
        bp.free(held[i])
    assert bp.fragmentation() == 0.0


def test_defragment_ascending_run_property():
    """After compaction the free list hands out ASCENDING, contiguous-when-
    possible id runs (LIFO pop order), and the metric never increases."""
    bp = BlockPool(num_blocks=33, block_size=4)
    held = [bp.alloc(1) for _ in range(32)]
    import random
    random.Random(7).shuffle(held)
    for ids in held[:24]:                # free in shuffled order
        bp.free(ids)
    before = bp.fragmentation()
    after = bp.defragment()
    assert after <= before
    # ascending-run property: subsequent allocations pop ascending ids,
    # and with every lower id free the run is perfectly contiguous
    got = bp.alloc(24)
    assert all(b > a for a, b in zip(got, got[1:]))  # strictly ascending
    # the freed ids come back as the sorted set itself: one pass, no holes
    # beyond those still held by the live allocations
    assert got == sorted(got)


def test_double_free_raises_and_frees_nothing_further():
    """The double-free ValueError path: a batch containing an already-free
    id raises, ids BEFORE the bad one in the batch are returned (the loop
    is not transactional — documented behaviour), nothing after."""
    bp = BlockPool(num_blocks=8, block_size=2)
    a = bp.alloc(3)
    b = bp.alloc(2)
    bp.free([a[0]])
    free_before = bp.num_free
    with pytest.raises(ValueError, match=f"free of block {a[0]}"):
        bp.free([a[1], a[0], a[2]])      # a[1] freed, a[0] double-free
    assert bp.num_free == free_before + 1      # only a[1] made it back
    assert bp.num_allocated == 1 + len(b)      # a[2] still held
    bp.free([a[2]] + b)                        # and still freeable


def test_grow_table_extends_in_place_and_is_all_or_nothing():
    """Mid-decode growth: grow_table appends the granted ids to the row's
    block list; on exhaustion it returns None and takes nothing (the
    engine's preemption signal)."""
    bp = BlockPool(num_blocks=6, block_size=4)
    mine = bp.alloc(2)
    snapshot = list(mine)
    got = bp.grow_table(mine, 2)
    assert got is not None and mine == snapshot + got
    assert bp.num_free == 1
    assert bp.grow_table(mine, 2) is None      # all-or-nothing: 1 < 2
    assert bp.num_free == 1 and len(mine) == 4
    bp.free(mine)


# ------------------------------------------------------- gather / scatter
def test_scatter_gather_roundtrip_and_sink():
    cfg = get_config("stablelm-1.6b").smoke()
    pool = init_kv_pool(cfg, num_blocks=8, block_size=4)
    N, L, two, R, bs, W = pool.shape
    assert two == 2                  # K and V stacked: one-scatter appends
    KV, hd = cfg.num_kv_heads, cfg.hd
    assert R * W == KV * hd          # heads packed into lane rows
    S = 6
    rng = np.random.default_rng(0)
    krow = jnp.asarray(rng.standard_normal((L, KV, S, hd)), pool.dtype)
    vrow = jnp.asarray(rng.standard_normal((L, KV, S, hd)), pool.dtype)
    blocks = jnp.asarray([3, 5], jnp.int32)
    pool = scatter_prefill_row(pool, blocks, krow, vrow)
    tables = jnp.zeros((1, 3), jnp.int32).at[0, :2].set(blocks)
    ks, vs = gather_pages(pool, 0, tables, hd)   # (1, KV, 3*bs, hd) each
    np.testing.assert_array_equal(np.asarray(ks[0, :, :S]),
                                  np.asarray(krow[0]))
    np.testing.assert_array_equal(np.asarray(vs[0, :, :S]),
                                  np.asarray(vrow[0]))
    # table tail points at the sink: those positions read zeros
    np.testing.assert_array_equal(np.asarray(ks[0, :, 2 * bs:]), 0.0)

    # append the 7th token (block idx 1, offset 2) on the active row of
    # the LAST layer: K and V land in ONE in-place scatter on the whole pool
    new_k = jnp.full((1, KV, hd), 7.0, pool.dtype)
    new_v = jnp.full((1, KV, hd), 5.0, pool.dtype)
    p_act = append_kv(pool, L - 1, new_k, new_v, tables,
                      jnp.asarray([S], jnp.int32), jnp.asarray([True]))
    ks2, vs2 = gather_pages(p_act, L - 1, tables, hd)
    np.testing.assert_array_equal(np.asarray(ks2[0, :, S]),
                                  np.asarray(new_k[0]))
    np.testing.assert_array_equal(np.asarray(vs2[0, :, S]),
                                  np.asarray(new_v[0]))
    # the other layers' pages are untouched
    np.testing.assert_array_equal(np.asarray(p_act[:, :L - 1]),
                                  np.asarray(pool[:, :L - 1]))
    # inactive row: the write is redirected to the sink block
    p_in = append_kv(pool, 0, new_k * 9, new_v * 9, tables,
                     jnp.asarray([S], jnp.int32), jnp.asarray([False]))
    np.testing.assert_array_equal(np.asarray(p_in[3:6]),
                                  np.asarray(pool[3:6]))
    assert np.any(np.asarray(p_in[SINK_BLOCK, 0, 0]) == 63.0)
    assert np.any(np.asarray(p_in[SINK_BLOCK, 0, 1]) == 45.0)


def test_scatter_token_window_and_table_extension():
    """Chunked-prefill window scatter through the tables + the device-side
    per-row table-extension scatter used by mid-decode growth."""
    from repro.serve.kvcache import (extend_block_tables,
                                     scatter_token_window, set_table_rows)
    cfg = get_config("stablelm-1.6b").smoke()
    pool = init_kv_pool(cfg, num_blocks=8, block_size=4)
    KV, hd = cfg.num_kv_heads, cfg.hd
    B, mb = 2, 4
    tables = jnp.zeros((B, mb), jnp.int32)
    tables = set_table_rows(tables, jnp.asarray([1], jnp.int32),
                            jnp.asarray([[2, 3, 0, 0]], jnp.int32))
    # grow row 1 by one block at column 2 — in-place device scatter
    tables = extend_block_tables(tables, jnp.asarray([1], jnp.int32),
                                 jnp.asarray([2], jnp.int32),
                                 jnp.asarray([6], jnp.int32))
    np.testing.assert_array_equal(np.asarray(tables),
                                  [[0, 0, 0, 0], [2, 3, 6, 0]])
    # write a 5-token window starting at position 6 on row 1 (crosses the
    # block-1 -> block-2 boundary); row 0 invalid -> sink
    C = 5
    rng = np.random.default_rng(0)
    ks = jnp.asarray(rng.standard_normal((B, C, KV, hd)), pool.dtype)
    vs = jnp.asarray(rng.standard_normal((B, C, KV, hd)), pool.dtype)
    valid = np.zeros((B, C), bool)
    valid[1, :4] = True                  # 4 valid tokens, 1 past-prompt
    p0 = scatter_token_window(pool, 0, ks, vs, tables,
                              jnp.asarray([0, 6], jnp.int32),
                              jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(p0[:, 1:]),
                                  np.asarray(pool[:, 1:]))
    got_k, got_v = gather_pages(p0, 0, tables, hd)
    np.testing.assert_array_equal(np.asarray(got_k[1, :, 6:10]),
                                  np.asarray(ks[1, :4]).swapaxes(0, 1))
    np.testing.assert_array_equal(np.asarray(got_v[1, :, 6:10]),
                                  np.asarray(vs[1, :4]).swapaxes(0, 1))
    # row 0 (all invalid) and the past-prompt tail went to the sink: blocks
    # owned by nobody are untouched
    for untouched in (1, 4, 5, 7):
        np.testing.assert_array_equal(np.asarray(p0[untouched]),
                                      np.asarray(pool[untouched]))


def test_init_kv_pool_rejects_ssm():
    cfg = get_config("falcon-mamba-7b").smoke()
    with pytest.raises(ValueError, match="attention"):
        init_kv_pool(cfg, 8, 4)


def test_set_carry_rows_scatter():
    from repro.serve.kvcache import set_carry_rows
    lengths = jnp.asarray([5, 0, 9, 0], jnp.int32)
    last = jnp.asarray([11, 0, 12, 0], jnp.int32)
    rem = jnp.asarray([3, 0, 1, 0], jnp.int32)
    # seat rows 1 and 3; pad with a repeat of row 3 (idempotent)
    rows = jnp.asarray([1, 3, 3], jnp.int32)
    ln, la, rm = set_carry_rows(
        lengths, last, rem, rows,
        jnp.asarray([7, 4, 4], jnp.int32),
        jnp.asarray([21, 22, 22], jnp.int32),
        jnp.asarray([8, 6, 6], jnp.int32))
    np.testing.assert_array_equal(np.asarray(ln), [5, 7, 9, 4])
    np.testing.assert_array_equal(np.asarray(la), [11, 21, 12, 22])
    np.testing.assert_array_equal(np.asarray(rm), [3, 8, 1, 6])


def test_deferred_free_keeps_invariant_and_defragment():
    """Deferred blocks stay allocated for accounting, are skipped by
    defragment's free-list sort, and release in FIFO fence order."""
    bp = BlockPool(num_blocks=9, block_size=4)
    a = bp.alloc(4)
    b = bp.alloc(2)
    bp.free_deferred(a)
    bp.free(b)
    assert bp.num_free + bp.num_allocated == bp.num_blocks - 1
    assert bp.num_deferred == 4
    bp.defragment()                  # must not disturb deferred blocks
    assert bp.num_deferred == 4
    bp.release_deferred()
    bp.free_deferred(bp.alloc(1))    # second batch enters young stage
    assert bp.release_deferred() == 4
    assert bp.num_deferred == 1
    assert bp.release_deferred() == 1
    assert bp.num_free == bp.num_blocks - 1


# --------------------------------------------------- refcounts (prefix CoW)
def test_refcount_lifecycle_and_shared_accounting():
    """alloc -> rc 1, incref pins, each free drops ONE ref, release only at
    zero; num_shared counts rc>1 blocks; the num_free+num_allocated
    invariant never sees a shared block twice."""
    bp = BlockPool(num_blocks=9, block_size=4)
    ids = bp.alloc(3)
    assert all(bp.refcount(b) == 1 for b in ids)
    assert bp.num_shared == 0
    bp.incref(ids[:2])
    assert bp.refcount(ids[0]) == 2 and bp.num_shared == 2
    assert bp.num_free + bp.num_allocated == bp.num_blocks - 1
    bp.free(ids)                       # ids[2] released, ids[0:2] survive
    assert bp.num_free == 6 and bp.num_allocated == 2
    assert bp.refcount(ids[2]) == 0
    got = bp.alloc(6)                  # never re-hands a live-ref block
    assert ids[0] not in got and ids[1] not in got
    bp.free(got)
    bp.free(ids[:2])
    assert bp.num_free == bp.num_blocks - 1


def test_refcount_free_deferred_last_ref_only_fences():
    """free_deferred on a shared block just unpins; the LAST reference is
    what enters the fence — and a parked co-holder keeps the block out of
    defragment's way the whole time."""
    bp = BlockPool(num_blocks=6, block_size=4)
    ids = bp.alloc(2)
    bp.incref(ids)
    bp.free_deferred(ids)              # co-holder remains: no fence
    assert bp.num_deferred == 0
    assert all(bp.refcount(b) == 1 for b in ids)
    bp.defragment()                    # live-ref blocks not in free list
    bp.free_deferred(ids)              # last refs: fenced now
    assert bp.num_deferred == 2
    with pytest.raises(ValueError, match="not live"):
        bp.incref(ids[:1])             # deferred blocks are un-pinnable
    bp.release_deferred()
    assert bp.release_deferred() == 2
    assert bp.num_free == bp.num_blocks - 1


def test_defragment_raises_on_live_block_in_free_list():
    """Regression for the refcount-era defragment: a live or fenced id in
    the free list means the accounting is corrupt — sort must refuse
    instead of silently blessing a block some table still reads."""
    bp = BlockPool(num_blocks=6, block_size=4)
    ids = bp.alloc(2)
    bp._free.append(ids[1])            # simulate upstream corruption
    with pytest.raises(RuntimeError, match="corrupt"):
        bp.defragment()
    bp._free.remove(ids[1])
    bp.free(ids)
    assert bp.defragment() == 0.0


def test_copy_blocks_forks_without_touching_source():
    """The CoW device primitive: dst pages become bit-copies of src pages,
    src pages and every other page are untouched, and the SINK->SINK
    padding convention is a harmless self-copy."""
    from repro.serve.kvcache import copy_blocks
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(6, 2, 2, 1, 4, 3)), jnp.float32)
    before = np.asarray(pool)
    out = np.asarray(copy_blocks(
        pool, jnp.asarray([2, 4, SINK_BLOCK], jnp.int32),
        jnp.asarray([5, 1, SINK_BLOCK], jnp.int32)))
    np.testing.assert_array_equal(out[5], before[2])
    np.testing.assert_array_equal(out[1], before[4])
    for untouched in (0, 2, 3, 4):
        np.testing.assert_array_equal(out[untouched], before[untouched])
