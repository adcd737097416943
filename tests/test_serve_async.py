"""Async decode lookahead: async-vs-sync parity and the new scheduling
hazards it must solve (one-chunk-late retirement, seat-generation token
discard, deferred-free fence).

Parity tests pin ``paged_impl="gather"`` — the bit-exact oracle read path
— so EXACT token equality against the synchronous engine is structural
(the xla/pallas online softmax reorders bf16 reductions; see
``test_serve_continuous.py``). The async engine runs the SAME compiled
chunk program on the same carry values, so its streams must match
token-for-token under every admission pattern: chunked prefill,
mid-decode block-table growth, preemption-requeue, and SSM/hybrid slot
serving."""
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.serve.engine import ServeEngine
from repro.serve.kvcache import BlockPool


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("stablelm-1.6b").smoke()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _pool_restored(eng) -> bool:
    """Every non-parked block back on the free list. With the prefix cache
    on, retired prompts' blocks stay PARKED (rc 1, trie-held) rather than
    free — they are reclaimable on demand, so the drain invariant counts
    them."""
    parked = eng._prefix.num_parked if eng._prefix is not None else 0
    return eng._pool.num_free + parked == eng._pool.num_blocks - 1


def _both_modes(cfg, params, prompts, max_new, **kw):
    outs = {}
    engines = {}
    for mode in (False, True):
        with ServeEngine(cfg, params, async_decode=mode, **kw) as eng:
            outs[mode] = eng.generate(prompts, max_new=max_new)
            engines[mode] = eng
    return outs[False], outs[True], engines[True]


def test_async_parity_mixed_lengths(setup):
    """Mixed-length prompts through one admission group: async greedy
    tokens are bit-identical to the synchronous engine on the oracle."""
    cfg, params = setup
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=s).astype(np.int32)
               for s in (4, 7, 4, 5)]
    sync, async_, eng = _both_modes(cfg, params, prompts, 6,
                                    decode_chunk=4, paged_impl="gather")
    for s, a in zip(sync, async_):
        np.testing.assert_array_equal(s, a)
    assert eng.stats["decode_cycles"] >= 1


def test_async_parity_chunked_prefill(setup):
    """A prompt longer than the prefill window streams windows while a
    resident row decodes; completion is deferred one cycle in async mode
    but the streams stay bit-identical."""
    cfg, params = setup
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=4).astype(np.int32),
               rng.integers(1, cfg.vocab_size, size=20).astype(np.int32)]
    sync, async_, eng = _both_modes(
        cfg, params, prompts, 10, decode_chunk=2, block_size=4,
        prefill_chunk=8, paged_impl="gather")
    for s, a in zip(sync, async_):
        np.testing.assert_array_equal(s, a)
    assert eng.stats["prefill_windows"] >= 2


def test_async_parity_growth_and_preemption(setup):
    """Tight pool: both rows admit on prompt-only footprint, grow
    mid-decode, and pool exhaustion preempts the youngest — whose
    in-flight chunk tokens are discarded (seat generation) and whose
    re-run emits an identical stream. Every block returns to the pool
    (the deferred-free fence drains)."""
    cfg, params = setup
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size, size=16).astype(np.int32)
               for _ in range(2)]
    kw = dict(decode_chunk=4, kv_blocks=10, block_size=4,
              paged_impl="gather")
    with ServeEngine(cfg, params, **kw) as s_eng:
        sync = s_eng.generate(prompts, max_new=16)
    with ServeEngine(cfg, params, async_decode=True, **kw) as a_eng:
        reqs = [a_eng.submit(p, max_new=16) for p in prompts]
        async_ = [a_eng.result(r, timeout=240) for r in reqs]
        stats = dict(a_eng.stats)
    assert stats["grown_blocks"] >= 1
    assert stats["preempted"] >= 1
    assert any(r.preempted_count >= 1 for r in reqs)
    for s, a in zip(sync, async_):
        np.testing.assert_array_equal(s, a)
    # fence fully drained: every block found its way back
    assert a_eng._pool.num_deferred == 0
    assert _pool_restored(a_eng)


@pytest.mark.parametrize("arch", ["falcon-mamba-7b", "zamba2-1.2b"])
def test_async_parity_ssm_slots(arch):
    """SSM/hybrid slot serving under the async carry: the state pool and
    the (lengths, last, rem) carry stay device-resident, streams match the
    synchronous engine exactly (row-wise math — no oracle pin needed)."""
    cfg = get_config(arch).smoke()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [np.arange(1, 6, dtype=np.int32),
               np.arange(2, 10, dtype=np.int32),
               np.arange(4, 9, dtype=np.int32)]
    sync, async_, eng = _both_modes(cfg, params, prompts, 6,
                                    decode_chunk=2, max_seq_len=64)
    assert not eng.paged
    for s, a in zip(sync, async_):
        np.testing.assert_array_equal(s, a)
    assert eng.stats["retired"] == 3


def test_async_dispatch_precedes_sync_in_stage_log(setup):
    """The decode stage is split dispatch -> sync: each cycle's log shows
    the NEXT chunk dispatched before the PREVIOUS chunk's tokens are
    synced (the sync event names the dispatch cycle it drains), i.e. the
    pipeline really runs one chunk deep."""
    cfg, params = setup
    with ServeEngine(cfg, params, decode_chunk=2, async_decode=True,
                     record_stages=True, paged_impl="gather") as eng:
        eng.generate([np.arange(1, 6, dtype=np.int32)], max_new=12)
        ev = [(s, tok, info) for s, tok, info, _ in eng.stage_log
              if s in ("dispatch", "sync")]
    # at least one cycle shows dispatch(token k) followed by sync(token k)
    # draining an EARLIER dispatch token
    paired = [(tok, info[0]) for s, tok, info in ev if s == "sync"]
    assert paired, f"no sync events in {ev}"
    assert all(prev < tok for tok, prev in paired)
    disp = {tok for s, tok, _ in ev if s == "dispatch"}
    assert all(prev in disp for _, prev in paired)
    # one-chunk-late drain: some cycle both dispatched new work AND synced
    # the previous chunk (true depth-2 overlap, not alternation)
    sync_toks = {tok for s, tok, _ in ev if s == "sync"}
    assert disp & sync_toks


def test_async_tight_pool_stall_yields_to_resident(setup):
    """Pool so tight every sequence must grow into ALL usable blocks: the
    admission gate lets the STALLED resident claim fence-released blocks
    before new admissions (without it, admit/preempt livelock: the waiting
    request re-admits, takes the released block, and is immediately
    preempted to feed the older row — forever). All requests complete,
    streams bit-identical, pool restored."""
    cfg, params = setup
    prompts = [np.arange(1, 5, dtype=np.int32) for _ in range(3)]
    kw = dict(decode_chunk=4, kv_blocks=5, block_size=4,
              paged_impl="gather")
    with ServeEngine(cfg, params, **kw) as s_eng:
        sync = s_eng.generate(prompts, max_new=12)
    with ServeEngine(cfg, params, async_decode=True, **kw) as a_eng:
        async_ = a_eng.generate(prompts, max_new=12)
        stats = dict(a_eng.stats)
    assert stats["retired"] == 3
    for s, a in zip(sync, async_):
        np.testing.assert_array_equal(s, a)
    assert a_eng._pool.num_deferred == 0
    assert _pool_restored(a_eng)


# ------------------------------------------------------- deferred-free fence
def test_blockpool_deferred_free_fence():
    """free_deferred parks blocks behind TWO release_deferred advances;
    they stay accounted as allocated (invariant holds), invisible to
    alloc, and double-free of a deferred block raises."""
    pool = BlockPool(8, 4)
    ids = pool.alloc(4)
    rest = pool.alloc(3)
    assert pool.num_free == 0
    pool.free_deferred(ids)
    assert pool.num_deferred == 4
    assert pool.num_free == 0                      # invisible to alloc
    assert pool.num_free + pool.num_allocated == pool.num_blocks - 1
    with pytest.raises(ValueError, match="deferred"):
        pool.free(ids[:1])                         # double free via free()
    with pytest.raises(ValueError):
        pool.free_deferred(ids[:1])                # and via free_deferred()
    assert pool.release_deferred() == 0            # young -> old: not yet
    assert pool.alloc(1) is None
    assert pool.release_deferred() == 4            # old -> free list
    assert pool.num_deferred == 0
    got = pool.alloc(4)
    assert got is not None and sorted(got) == sorted(ids)
    pool.free(got)
    pool.free(rest)
    assert pool.num_free == pool.num_blocks - 1


def test_engine_fence_blocks_not_reallocated_while_chunk_in_flight(setup):
    """Engine-level fence proof: wrap the pool so every alloc/grow result
    is checked against the live deferred set — a preempted row's blocks
    must never be handed out before two fence advances (i.e. while a chunk
    that may still write them is in flight)."""
    cfg, params = setup
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size, size=16).astype(np.int32)
               for _ in range(2)]
    with ServeEngine(cfg, params, decode_chunk=4, kv_blocks=10,
                     block_size=4, paged_impl="gather",
                     async_decode=True) as eng:
        pool = eng._pool
        lock = threading.Lock()
        young, old = set(), set()   # mirror of the pool's two fence stages
        defers = []
        violations = []
        orig_alloc, orig_fd = pool.alloc, pool.free_deferred
        orig_rel = pool.release_deferred

        def alloc(n, **kw):      # use_reserved= passes through untouched
            ids = orig_alloc(n, **kw)
            with lock:
                if ids and (young | old) & set(ids):
                    violations.append(("alloc", ids))
            return ids

        def free_deferred(ids):
            with lock:
                # only a block's LAST reference enters the fence: a shared
                # id (prefix-cache co-holder) is merely unpinned and may
                # later be evicted/freed/reallocated legitimately
                fenced = [b for b in ids if pool.refcount(b) == 1]
                young.update(fenced)
                if fenced:
                    defers.append(fenced)
            orig_fd(ids)

        def release_deferred():
            with lock:
                # mirror the pool: the current `old` stage becomes
                # allocatable after this advance, `young` ages into `old`
                old.clear()
                old.update(young)
                young.clear()
            return orig_rel()

        pool.alloc = alloc
        pool.free_deferred = free_deferred
        pool.release_deferred = release_deferred
        reqs = [eng.submit(p, max_new=16) for p in prompts]
        outs = [eng.result(r, timeout=240) for r in reqs]
        assert eng.stats["preempted"] >= 1
        if eng._prefix is None or eng._prefix.num_parked == 0:
            assert defers, "preemption never went through the deferred fence"
        else:
            # prefix-cache leg: the preempted row's blocks can ALL be
            # index-registered — then refcounts (parked, unreachable by
            # alloc while referenced) are the protection path, and only
            # last-reference drops would have fenced
            assert eng.stats["preempted"] >= 1
        assert not violations, violations
        assert all(o.shape == (16,) for o in outs)


#: greedy tokens of the engine below as served before the pool moved to the
#: in-place, packed ``(N, L, 2, R, block, W)`` layout (KV pool carried
#: through the layer loop instead of scanned as xs/ys): the rewrite must
#: not move a single token on the oracle read path
_GOLDEN_PRE_INPLACE = [
    [287, 291, 409, 292, 269, 200, 180, 198, 244, 114, 378, 180, 410],
    [446, 27, 457, 473, 80, 484, 466, 114, 199, 199, 148, 89, 348],
    [166, 182, 74, 87, 78, 108, 59, 239, 131, 61, 366, 91, 285],
    [30, 347, 394, 394, 71, 71, 347, 394, 356, 180, 197, 41, 71],
]


@pytest.mark.parametrize("async_decode", [False, True])
def test_inplace_pool_keeps_golden_tokens(setup, async_decode):
    """Chunked prefill (prompts up to 30 tokens through 8-token windows)
    and mid-decode block growth (4-token blocks, 13 new tokens) on the
    gather oracle reproduce the pre-rewrite greedy tokens exactly."""
    cfg, params = setup
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, size=s).astype(np.int32)
               for s in (5, 23, 11, 30)]
    with ServeEngine(cfg, params, async_decode=async_decode,
                     paged_impl="gather", decode_chunk=3, block_size=4,
                     prefill_chunk=8, kv_blocks=64, max_batch=4) as eng:
        outs = eng.generate(prompts, max_new=13)
        assert eng.stats["prefill_windows"] >= 2
        assert eng.stats["grown_blocks"] > 0
    assert [list(map(int, o)) for o in outs] == _GOLDEN_PRE_INPLACE
