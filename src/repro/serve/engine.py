"""Continuously-batched serving engine: a RESIDENT 4-stage pipeline fed by
a request queue, with TWO-PHASE memory admission.

PR 2 kept ONE cyclic :class:`repro.pipeline.DataPipeline` alive for the
life of the engine; PR 3 made the paged *read* path occupancy-proportional.
This revision makes the *write/admission* half follow live token counts
too — the Taskflow memory thesis (resources follow control flow
incrementally, not worst-case up front) applied to KV admission:

    admit (SERIAL)    -> pop an admission group from ONE FIFO (no length
                         buckets: chunked prefill makes per-window shapes
                         uniform, so mixed-length groups admit together) and
                         allocate its PROMPT-ONLY block footprint; park via
                         ``pf.defer(token)`` when the head does not fit, or
                         emit a plain decode-pump cycle
    prefill (SERIAL)  -> one compiled launch for the group's FIRST prompt
                         window (fixed window size, prompts right-padded);
                         SSM/hybrid archs prefill each member's whole prompt
                         here instead (recurrent state is O(1)/sequence)
    decode (SERIAL,   -> merge the group (scatter window-0 KV / recurrent
      accel domain)      state into the pool, assign slots), stream ONE more
                         prefill window for every mid-prefill row, grow
                         block tables lazily for rows about to cross a block
                         boundary (preempting the youngest row on pool
                         exhaustion), then advance every decoding row by one
                         compiled chunk of ``decode_chunk`` steps
    complete (PARALLEL)-> retire rows that just finished: fulfil their
                         request futures, free their blocks/slots — per
                         sequence, WITHOUT draining the pipeline

Two-phase admission
-------------------
*Phase 1 (admit):* a request is admitted when the pool covers its PROMPT
KV footprint — not ``prompt + max_new``. *Phase 2 (grow):* every
``block_size`` decode tokens, the decode stage grants the row one more
block (``BlockPool.grow_table`` + a device-side table-extension scatter);
on pool exhaustion it preempts the YOUNGEST resident row back onto the
wait queue (its blocks freed, its request re-queued at the head) instead
of deadlocking. Long prompts are *chunked*: window 0 lands via the prefill
stage, the rest stream through the decode stage one fixed-size window per
cycle, scattered straight into the paged pool — resident rows keep
decoding in the overlapped cycles.

The KV pool and the block-table array are written ONLY by the SERIAL
decode stage, so pool updates stay single-writer by construction; the
block table is device-resident across cycles (growth is an in-place
scatter, not a re-upload). The compiled chunk reads the pool gather-free
(``paged_impl``: the Pallas kernel or its XLA page-loop lowering, see
:mod:`repro.serve`).

Async decode lookahead (``async_decode=`` / ``REPRO_ASYNC_DECODE``)
-------------------------------------------------------------------
The synchronous decode stage blocks on every chunk's tokens and runs all
grow/preempt/retire/admit bookkeeping while the device idles. With
``async_decode=True`` the stage is split into **dispatch -> sync** at a
pipeline depth of 2:

* the decode carry (``lengths``, ``last``, ``rem``) is DEVICE-RESIDENT
  across cycles, alongside the block tables: chunk N+1 consumes chunk N's
  output carry directly, so the device-side dependency chain never waits
  on the host. Merge/retire/preempt mutate the carry through the same
  fixed-shape padded scatters the table array uses
  (:func:`repro.serve.kvcache.set_carry_rows`); the host keeps exact
  ``lengths``/``rem`` mirrors by pure arithmetic (chunk advance is
  token-independent) while ``last`` lives only on device.
* each cycle dispatches chunk N+1 FIRST (JAX async dispatch queues it
  behind N), then syncs chunk N's tokens and does every piece of host
  bookkeeping — emit tokens, collect finished rows, stream prefill
  windows, grow tables — while N+1 runs on device. Admission scatters,
  window launches and growth scatters are sequenced BEFORE the dispatch.

The new scheduling hazards this opens are closed explicitly:

* **retirement is one chunk late**: a row that exhausts ``rem`` during
  chunk N stays seated through N+1 — masked on device by ``rem == 0``
  (KV writes go to the sink) — and detaches after N's sync; tokens a
  chunk computed for a row whose seat changed since dispatch (preempted,
  retired, re-seated) are discarded host-side via a per-slot seat
  generation.
* **deferred-free fence**: a preempted row's blocks may still be written
  by the chunk in flight at preemption time (and by the prefill window
  launched the same cycle), so :meth:`repro.serve.kvcache.BlockPool
  .free_deferred` parks them — invisible to allocation — until the
  engine has synced past that device work (two fence advances).
* **prefill-window completion is deferred one cycle**: the window launch
  precedes the next chunk on the pool's dependency chain, so reading its
  first-token logits a cycle later never stalls the loop behind the
  in-flight chunk.

Greedy tokens are bit-identical to the synchronous engine (same compiled
chunk program, same carry values — asserted on the ``gather`` oracle in
``tests/test_serve_async.py``); the synchronous path remains the
reference. Each cycle's phases are ``serve.*`` spans
(:func:`repro.obs.tracing.span`): ``serve.dispatch``, ``serve.sync`` and
``serve.bookkeeping`` in both loops, so a ``jax.profiler`` trace shows
which host phase the device waited on.

SSM / hybrid architectures (mamba, zamba2) serve through the SAME
resident pipeline via a fixed-slot recurrent-state pool: prefilled
``(conv, h)`` states (plus zamba2's shared-block KV span) are scattered
into a per-slot pool, rows decode side by side at per-row positions
(:func:`repro.models.lm.decode_step_slots`), and slots free at
retirement. The old per-call grouped fallback is retired from
``submit()``/``generate()`` and survives only as the benchmark baseline
(:meth:`ServeEngine._generate_grouped`).

Client API: :meth:`submit` returns a :class:`ServeRequest` future;
:meth:`ServeRequest.result` blocks for the tokens. :meth:`generate` remains
as a thin compatibility shim over submit/result (greedy tokens bit-identical
to the per-call engine it replaces).

The pipeline goes idle (stop-drain) when no requests are waiting or
running; ``submit()`` re-arms it without rebuilding the task graph
(:meth:`repro.pipeline.Pipeline.run` on the same resident grid). A failure
inside any stage cancels the topology, fails every outstanding request
future (``result()`` raises instead of deadlocking) and marks the engine
broken.
"""
from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..core import ACCEL, HOST, Executor
from ..distributed.sharding import (ShardCtx, manual_serve_map,
                                    serve_attn_sharded, serve_kv_cache_spec,
                                    serve_param_shardings, serve_pool_spec,
                                    serve_tp_size, use_shard_ctx,
                                    validate_serve_mesh)
from ..models import lm
from ..obs import TRACK_ENGINE
from ..obs import from_env as _obs_from_env
from ..obs.tracing import span
from ..pipeline import DataPipe, DataPipeline, PipeType
from .errors import (DeadlineExceeded, EngineClosed, Overloaded,
                     RequestCancelled, RowFailed, SnapshotCorrupt,
                     WatchdogTimeout)
from .faultinject import FaultInjected, FaultInjector
from .journal import Journal, replay as replay_journal
from .kvcache import (SINK_BLOCK, BlockPool, copy_blocks,
                      extend_block_tables, init_kv_pool,
                      scatter_prefill_rows, set_carry_rows, set_table_rows)
from .prefix import PrefixCache
from .scheduler import Scheduler, ServeRequest
from .snapshot import corrupt_snapshot, read_snapshot, write_snapshot

__all__ = ["ServeEngine", "ServeRequest", "JOURNAL_FILE", "SNAPSHOT_FILE"]

#: File names ``recover()`` / ``launch.serve --state-dir`` use inside a
#: state directory.
JOURNAL_FILE = "journal.wal"
SNAPSHOT_FILE = "engine.snap"


def _env_mesh_ctx(cfg: ModelConfig) -> Optional[ShardCtx]:
    """Resolve ``REPRO_MESH_MODEL=N`` into a serve :class:`ShardCtx` (or
    None for single-device). The requested model-axis size is CLAMPED —
    first to the local device count, then down to the largest size that
    divides the config's KV-head/head/feature counts — so the env knob is
    safe to export across a whole test matrix of configs. An explicit
    ``ctx=`` argument to :class:`ServeEngine` is never clamped: an
    indivisible mesh there raises
    :class:`repro.distributed.sharding.MeshDivisibilityError`.

    SSM/hybrid configs have no KV heads to partition, so the env path
    leaves them on a single device (their slot state is replicated by
    construction anyway)."""
    env = os.environ.get("REPRO_MESH_MODEL", "").strip()
    if not env:
        return None
    mp = min(int(env), jax.device_count())
    if cfg.ssm or cfg.hybrid_attn_every:
        return None
    while mp > 1 and not serve_attn_sharded(cfg, mp):
        mp -= 1
    if mp <= 1:
        return None
    from ..launch.mesh import make_ctx, small_mesh
    return make_ctx(small_mesh(data=1, model=mp))


class ServeEngine:
    """Resident continuous-batching engine (see module docstring).

    Parameters
    ----------
    ctx:
        a :class:`repro.distributed.sharding.ShardCtx` over a device mesh
        with a ``model`` axis: the paged KV pool and the attention/MLP
        projection weights are partitioned by KV head / output column
        across it and every compiled step runs under ``shard_map``
        (greedy tokens stay BIT-IDENTICAL to the single-device engine —
        the tensor-parallel decomposition only ever concatenates
        column slices, see ``docs/sharded_serving.md``). A model-axis
        size that does not divide the config's KV-head/head/feature
        counts raises a typed :class:`repro.distributed.sharding
        .MeshDivisibilityError`. None resolves via the
        ``REPRO_MESH_MODEL`` env var (clamped to the device count and
        the largest divisible size; default single-device).
    decode_chunk:
        decode steps per compiled chunk launch — also the admission
        granularity (sequences join/leave at chunk boundaries).
    prefill_chunk:
        prompt tokens per prefill window. A prompt longer than this
        prefills across multiple pipeline cycles (window 0 in the prefill
        stage, the rest streamed by the decode stage) while resident rows
        keep decoding. Defaults to ``decode_chunk * block_size``. Paged
        (attention) archs only; SSM/hybrid prompts prefill whole.
    max_batch:
        decode slot count; the compiled chunk program always runs this many
        rows (inactive rows are masked), so batch composition changes never
        recompile.
    kv_blocks / block_size:
        paged KV pool geometry. Block 0 is the reserved sink.
    max_admit:
        cap on requests admitted per cycle (one prefill launch).
    max_seq_len:
        per-sequence cap on ``prompt + max_new`` (sets the block-table
        width; for zamba2 it sizes the shared-block KV span per slot).
        Defaults to 32 blocks worth, clamped to the pool size (512 for
        SSM/hybrid).
    paged_impl:
        attention read path of the compiled decode chunk: ``"pallas"``
        (gather-free Pallas kernel, Mosaic on TPU), ``"xla"`` (gather-free
        traced-bound page loop), or ``"gather"`` (materializing reference
        oracle). None resolves via
        :func:`repro.kernels.ops.default_paged_impl` (honors the
        ``REPRO_PAGED_IMPL`` env var; pallas on TPU, xla elsewhere).
    async_decode:
        pipeline the decode loop one chunk deep: the carry stays
        device-resident, chunk N+1 is dispatched before chunk N's tokens
        are synced, and all host bookkeeping overlaps device compute (see
        the module docstring). None resolves via the ``REPRO_ASYNC_DECODE``
        env var (default off — the synchronous path is the reference).
    prefix_cache:
        share KV blocks across requests with a common prompt prefix: full
        prompt chunks are indexed in a :class:`repro.serve.prefix
        .PrefixCache` trie, cache-hit admissions seed their block table
        with the shared (refcount-pinned) blocks and budget/prefill only
        their uncached suffix, a shared tail block is copy-on-write forked
        before the first divergent write, and under pool pressure cold
        PARKED prefix blocks are evicted by reuse score before any
        resident row is preempted (see ``docs/prefix_caching.md``). None
        resolves via the ``REPRO_PREFIX_CACHE`` env var (default off —
        the uncached path is the bit-exact reference). Paged
        (attention) archs only; ignored for SSM/hybrid models.
    tier_targets:
        per-priority-tier guaranteed minimum share of each admission
        cycle (``{tier: share}``, see :class:`repro.serve.scheduler
        .Scheduler`) — the anti-starvation floor for best-effort tiers
        under sustained SLO-tier load.
    shed_budget_s:
        load-shedding latency budget: a float applies one queue-wait
        budget to every tier, a dict maps ``{tier: budget_s}`` (tiers
        absent from the dict are never shed). ``submit()`` rejects with
        a typed :class:`repro.serve.errors.Overloaded` when the
        estimated queue wait exceeds the budget (or the request's own
        ``deadline_s``, making it unreachable before it ever queues).
        The estimate is a SERVICE-RATE model: observed decode
        throughput (EWMA tokens/s over engine cycles) divides the
        resident rows' remaining decode work plus the tier-visible
        waiting ``max_new`` backlog. Until the engine has emitted its
        first tokens it falls back to the p90 of the live
        ``serve.queue_wait_s`` histogram scaled by the backlog (armed
        after 8 recorded admissions — a cold-start engine never
        sheds); the fallback needs ``obs``, the rate model does not.
        None resolves via the ``REPRO_SHED_BUDGET_S`` env var (a
        float; default off).
    watchdog_s:
        engine watchdog budget in seconds: a daemon thread fails every
        in-flight/waiting future with a diagnostic
        :class:`repro.serve.errors.WatchdogTimeout` when a busy engine
        makes no cycle progress for this long (a wedged device sync, a
        deadlocked stage). 0/None = off; None resolves via the
        ``REPRO_WATCHDOG_S`` env var.
    fault_inject:
        a :class:`repro.serve.faultinject.FaultInjector` (or its spec
        string) injecting deterministic seeded faults at named engine
        sites — see :mod:`repro.serve.faultinject` for the grammar and
        sites. None resolves via the ``REPRO_FAULT_INJECT`` env var
        (default off).
    record_stages:
        keep an in-memory (stage, cycle-token, info, t) event log — the
        observer hook the overlap tests read.
    obs:
        a :class:`repro.obs.Observability` (tracer + metrics registry).
        The engine records request lifecycle spans on per-slot tracks,
        engine-cycle phase spans on the ``"engine"`` track, and the
        counters/gauges/histograms listed in :mod:`repro.serve`'s
        observability section. None resolves via the ``REPRO_OBS`` env
        var (default off — the disabled path costs attribute checks
        only). Rebindable at idle via :meth:`set_obs`.
    """

    def __init__(self, cfg: ModelConfig, params,
                 ctx: Optional[ShardCtx] = None,
                 decode_chunk: int = 8,
                 prefill_chunk: Optional[int] = None,
                 executor: Optional[Executor] = None,
                 pipeline_lines: int = 3,
                 max_batch: int = 8,
                 kv_blocks: int = 128,
                 block_size: int = 16,
                 max_admit: int = 4,
                 max_seq_len: Optional[int] = None,
                 paged_impl: Optional[str] = None,
                 async_decode: Optional[bool] = None,
                 prefix_cache: Optional[bool] = None,
                 tier_targets: Optional[Dict[int, float]] = None,
                 shed_budget_s=None,
                 watchdog_s: Optional[float] = None,
                 fault_inject=None,
                 journal=None,
                 record_stages: bool = False,
                 obs=None):
        self.cfg = cfg
        if ctx is None:
            ctx = _env_mesh_ctx(cfg)       # REPRO_MESH_MODEL, clamped
        self.ctx = ctx or ShardCtx(mesh=None)
        #: model-axis (tensor-parallel) size of the serve mesh; 1 = the
        #: single-device reference engine
        self._tp = serve_tp_size(self.ctx)
        if self._tp > 1:
            # an explicit indivisible mesh is a typed error, not a clamp
            validate_serve_mesh(cfg, self._tp)
        #: True when the paged KV pool is partitioned over the model axis
        #: (attention archs on a >1 mesh); SSM/hybrid state is replicated
        self._pool_sharded = self.ctx.mesh is not None \
            and serve_attn_sharded(cfg, self._tp)
        if self.ctx.mesh is not None:
            self._repl_ns = NamedSharding(self.ctx.mesh, P())
            # KV-head-partitioned attention + column-sharded MLP weights;
            # every other leaf (embeddings, norms, router, ...) replicated
            self.params = jax.device_put(
                params, serve_param_shardings(cfg, params, self.ctx))
        else:
            self._repl_ns = None
            self.params = params
        self.decode_chunk = decode_chunk
        self.pipeline_lines = pipeline_lines
        self._executor = executor
        self._own_executor = False
        self._prefill = jax.jit(self._prefill_impl,
                                static_argnames=("max_len",))
        self._decode_n = jax.jit(self._decode_n_impl,
                                 static_argnames=("n",),
                                 donate_argnums=(1,))

        #: continuous batching pages the attention KV cache; SSM/hybrid
        #: recurrent state is O(1)/seq and lives in a fixed-slot state pool
        self.paged = not (cfg.ssm or cfg.hybrid_attn_every)
        from ..kernels.ops import PAGED_IMPLS, default_paged_impl
        if paged_impl is not None and paged_impl not in PAGED_IMPLS:
            raise ValueError(f"paged_impl={paged_impl!r}: expected one of "
                             f"{PAGED_IMPLS} (or None for the default)")
        #: read path of the compiled decode chunk; None on non-paged archs
        self.paged_impl = (paged_impl or default_paged_impl()) \
            if self.paged else None
        if async_decode is None:
            async_decode = os.environ.get("REPRO_ASYNC_DECODE", "") \
                .strip().lower() in ("1", "true", "yes", "on")
        #: dispatch->sync pipelined decode loop (depth 2); False = the
        #: synchronous reference path
        self.async_decode = bool(async_decode)
        if prefix_cache is None:
            prefix_cache = os.environ.get("REPRO_PREFIX_CACHE", "") \
                .strip().lower() in ("1", "true", "yes", "on")
        #: cross-request KV block sharing (paged archs only); False = the
        #: uncached bit-exact reference path
        self.prefix_cache = bool(prefix_cache) and self.paged
        self._closing = False
        # graceful drain: set by drain() — admission stops, residents run
        # to completion; past _drain_deadline_at the decode stage
        # checkpoint-preempts every resident so close() can fail the
        # requeued work typed instead of hanging on it
        self._draining = False
        self._drain_deadline_at: Optional[float] = None
        # request WAL (durability boundary #1, off by default): a
        # repro.serve.journal.Journal or a path string; every lifecycle
        # transition appends one checksummed record. The None path is one
        # `is None` check per transition — bit-exact unchanged.
        if isinstance(journal, str):
            journal = Journal(journal)
        self._journal: Optional[Journal] = journal
        self._broken: Optional[BaseException] = None
        self._stage_log = [] if record_stages else None
        self._log_lock = threading.Lock()

        # deterministic fault injection (param > env; see faultinject.py)
        if fault_inject is None:
            fault_inject = os.environ.get("REPRO_FAULT_INJECT") or None
        if isinstance(fault_inject, str):
            fault_inject = FaultInjector.parse(fault_inject)
        self._fi: Optional[FaultInjector] = fault_inject
        # load-shedding budget: float (all tiers) or {tier: budget_s}
        if shed_budget_s is None:
            env = os.environ.get("REPRO_SHED_BUDGET_S", "").strip()
            shed_budget_s = float(env) if env else None
        self._shed_budget = shed_budget_s
        if watchdog_s is None:
            env = os.environ.get("REPRO_WATCHDOG_S", "").strip()
            watchdog_s = float(env) if env else 0.0
        self._watchdog_s = float(watchdog_s or 0.0)
        # service-rate load-shed model: EWMA of observed decode throughput
        # (emitted tokens per engine-cycle wall second), updated at every
        # chunk sync. 0.0 until the first tokens are emitted — the shed
        # estimator falls back to the p90-queue-wait heuristic until then.
        self._decode_rate = 0.0
        self._rate_alpha = 0.3

        B = max_batch
        self._scheduler = Scheduler(max_admit=max_admit,
                                    tier_targets=tier_targets)
        self._scheduler.on_event = self._sched_event
        # slot state: written by the SERIAL decode stage (merge/window/grow/
        # step) and the complete stage (free) under _state_lock; admit only
        # reads counts
        self._lengths = np.zeros((B,), np.int32)   # KV/state tokens written
        self._rem = np.zeros((B,), np.int32)       # decode steps remaining
        self._last = np.zeros((B,), np.int32)      # last emitted token
        # DEVICE-RESIDENT decode carry (lengths, last, rem): in async mode
        # chunk N+1 consumes chunk N's output carry directly (merge/grow/
        # retire/preempt mutate it via fixed-shape scatters) and the host
        # mirrors above are maintained deterministically — lengths/rem
        # arithmetic is token-independent, `last` is refreshed lazily from
        # synced chunk outputs. The sync path uploads the mirrors instead.
        self._carry = (self._dev(np.zeros((B,), np.int32)),
                       self._dev(np.zeros((B,), np.int32)),
                       self._dev(np.zeros((B,), np.int32)))
        self._set_carry = jax.jit(set_carry_rows)
        # seat generation per slot, bumped on every seat/retire/preempt:
        # guards late token emission in async mode (a synced chunk's tokens
        # only land on the seat they were computed for)
        self._slot_gen = np.zeros((B,), np.int64)
        self._pending: Optional[Dict[str, Any]] = None   # in-flight chunk
        self._window_pending: Optional[Dict[str, Any]] = None
        self._slot_req: List[Optional[ServeRequest]] = [None] * B
        self._slot_out: List[Optional[List[int]]] = [None] * B
        self._slot_phase: List[Optional[str]] = [None] * B  # prefill|decode
        self._free_slots = list(range(B - 1, -1, -1))
        self._slots_reserved = 0       # admitted but not yet merged
        self._inflight: set = set()    # admitted, not yet retired (failure
        #                                cleanup: these must see set_error)
        self._cycle_tokens: set = set()  # cycles minted and not yet completed
        # admitted groups not yet seated, keyed by cycle token: failure
        # isolation clears this so a stale group (admitted against the
        # pre-reset pool) is dropped at the merge instead of seating with
        # dead block ids
        self._premerge: Dict[int, List[ServeRequest]] = {}
        # bumped by every failure-isolation reset: retire payloads from an
        # older epoch must not free blocks / slots against the fresh state
        self._reset_epoch = 0
        self._state_lock = threading.Lock()
        self._pump_lock = threading.Lock()
        self._topo = None
        self._pipeline: Optional[DataPipeline] = None
        self.stats = {"admitted": 0, "admit_parks": 0, "pump_cycles": 0,
                      "decode_cycles": 0, "prefills": 0,
                      "prefill_windows": 0, "tokens_out": 0, "retired": 0,
                      "grown_blocks": 0, "preempted": 0, "stalls": 0,
                      "prefix_hits": 0, "prefix_tokens_saved": 0,
                      "cow_forks": 0, "shed": 0, "expired": 0,
                      "cancelled": 0, "watchdog_fires": 0,
                      "row_failures": 0, "recovered": 0,
                      "replayed_tokens": 0, "drain_preempted": 0,
                      "warm_started": 0}

        self._prefix: Optional[PrefixCache] = None
        self._kv_geom = (kv_blocks, block_size)   # failure-isolation reinit
        if self.paged:
            self._pool = BlockPool(kv_blocks, block_size)
            self._pkv = self._place_pool(
                init_kv_pool(cfg, kv_blocks, block_size,
                             tp=self._tp if self._pool_sharded else 1))
            if self.prefix_cache:
                self._prefix = PrefixCache(self._pool)
            if self._pool_sharded:
                # pool-touching mutators run per-shard under shard_map so
                # their donated in/out pool buffers keep the KV-head
                # sharding — a plain jit would let GSPMD re-lay them out
                pool_s = serve_pool_spec(cfg, self.ctx)
                kv_s = serve_kv_cache_spec(cfg, self.ctx)
                self._cow_copy = jax.jit(
                    manual_serve_map(copy_blocks, self.ctx,
                                     in_specs=(pool_s, P(), P()),
                                     out_specs=pool_s),
                    donate_argnums=(0,))
                self._scatter = jax.jit(
                    manual_serve_map(scatter_prefill_rows, self.ctx,
                                     in_specs=(pool_s, P(), kv_s, kv_s),
                                     out_specs=pool_s),
                    donate_argnums=(0,))
            else:
                self._cow_copy = jax.jit(copy_blocks, donate_argnums=(0,))
                self._scatter = jax.jit(self._scatter_impl,
                                        donate_argnums=(0,))
            self._max_seq = min(max_seq_len or 32 * block_size,
                                (kv_blocks - 1) * block_size)
            self.prefill_chunk = prefill_chunk or decode_chunk * block_size
            if self.prefill_chunk < 1:
                raise ValueError("prefill_chunk must be >= 1")
            mb = self._pool.blocks_for(self._max_seq)
            # block tables: host mirror for growth decisions + a DEVICE-
            # resident array the compiled programs read; growth/merge/retire
            # update the device copy with in-place scatters
            self._tables = np.zeros((B, mb), np.int32)
            self._tables_dev = self._dev(np.zeros((B, mb), np.int32))
            self._pref_pos = np.zeros((B,), np.int32)  # prompt tokens done
            self._slot_blocks: List[Optional[List[int]]] = [None] * B
            self._slot_prompt: List[Optional[np.ndarray]] = [None] * B
            # preallocated chunked-prefill window buffers: each cycle only
            # the rows actually mid-prefill are (re)written — invariant: a
            # row's `valid` entries are False unless it is mid-prefill
            # (cleared on decode transition and preemption)
            C = self.prefill_chunk
            self._wp_toks = np.zeros((B, C), np.int32)
            self._wp_valid = np.zeros((B, C), bool)
            self._wp_start = np.zeros((B,), np.int32)
            self._wp_last_idx = np.zeros((B,), np.int32)
            # worst-case blocks granted in one cycle: every row crosses into
            # ceil(decode_chunk / block_size) new blocks plus one boundary
            # block — the fixed width of the growth scatter
            self._grow_burst_max = B * (-(-decode_chunk // block_size) + 1)
            # async stall ledger: a row whose growth failed ONLY because the
            # needed blocks sit behind the deferred-free fence is masked on
            # device (rem -> 0) instead of preempted; its remaining steps
            # park here until the fence releases and growth succeeds
            self._stall_rem = np.zeros((B,), np.int32)
            self._set_rem = jax.jit(
                lambda rem, rows, vals: rem.at[rows].set(vals))
            self._decode_paged = jax.jit(self._decode_paged_impl,
                                         static_argnames=("n",),
                                         donate_argnums=(1,))
            self._prefill_window = jax.jit(self._prefill_window_impl,
                                           donate_argnums=(1,))
            self._extend_tables = jax.jit(extend_block_tables)
            self._set_rows = jax.jit(set_table_rows)
        else:
            self._max_seq = max_seq_len or 512
            self.prefill_chunk = None
            # fixed-slot recurrent-state pool: init_cache's pytree with the
            # scalar pos replaced by the per-row _lengths mirror
            self._sstate = {k: v
                            for k, v in lm.init_cache(cfg, B,
                                                      self._max_seq).items()
                            if k != "pos"}
            if self._repl_ns is not None:
                # SSM/hybrid slot state is replicated over the serve mesh
                self._sstate = jax.device_put(self._sstate, self._repl_ns)
            self._decode_slots = jax.jit(self._decode_slots_impl,
                                         static_argnames=("n",),
                                         donate_argnums=(1,))

        # observability: one open phase span per seated slot (name, t0);
        # None obs = fully disabled (hot paths guard on self._tr/_mh)
        self._slot_span: List[Optional[tuple]] = [None] * B
        self.set_obs(obs if obs is not None else _obs_from_env())

        # watchdog: a daemon thread that fails every outstanding future
        # when a BUSY engine makes no cycle progress within the budget
        # (stuck device sync, wedged stage) — result() raises a diagnostic
        # WatchdogTimeout instead of hanging
        self._wd_beat = time.perf_counter()
        self._wd_stop = threading.Event()
        self._wd_thread: Optional[threading.Thread] = None
        if self._watchdog_s > 0:
            self._wd_thread = threading.Thread(
                target=self._watchdog_loop, name="serve-watchdog",
                daemon=True)
            self._wd_thread.start()

    # ---------------------------------------------------------- observability
    def set_obs(self, obs) -> None:
        """Attach (or detach, with None) a :class:`repro.obs.Observability`.

        Binding caches every metric handle once (``self._mh``) and hands the
        metrics registry to the scheduler and block pool and the tracer to
        the resident pipeline, so an instrumented event costs one cached-
        handle call and a disabled one a single ``None`` check. Rebindable
        while the engine is idle — the overhead-gate benchmark toggles obs
        on ONE engine instead of paying a second jit warm-up.
        """
        self.obs = obs
        self._tr = obs.tracer if obs is not None else None
        metrics = obs.metrics if obs is not None else None
        self._scheduler.set_metrics(metrics)
        if self.paged:
            self._pool.set_metrics(metrics)
        if self._prefix is not None:
            self._prefix.set_metrics(metrics)
        if self._journal is not None:
            self._journal.set_metrics(metrics)
        if self._pipeline is not None:
            self._pipeline.tracer = self._tr
        #: per-tier TTFT histograms, keyed by priority — populated lazily
        #: at first token time (serve.ttft_s.tier<N>)
        self._mh_tier: Dict[int, Any] = {}
        if metrics is None:
            self._mh = None
            return
        self._mh = {
            "tokens_out": metrics.counter("serve.tokens_out"),
            "admitted": metrics.counter("serve.requests.admitted"),
            "retired": metrics.counter("serve.requests.retired"),
            "preempted": metrics.counter("serve.requests.preempted"),
            "stalled": metrics.counter("serve.requests.stalled"),
            "grown_blocks": metrics.counter("pool.grown_blocks"),
            "prefill_saved": metrics.counter("serve.prefill_tokens_saved"),
            "resident": metrics.gauge("serve.resident_rows"),
            "ttft": metrics.histogram("serve.ttft_s"),
            "qwait": metrics.histogram("serve.queue_wait_s"),
            "cycle": metrics.histogram("engine.cycle_s"),
            "dispatch": metrics.histogram("engine.dispatch_s"),
            "sync": metrics.histogram("engine.chunk_sync_s"),
            "book": metrics.histogram("engine.book_s"),
            "shed": metrics.counter("serve.shed"),
            "expired": metrics.counter("serve.expired"),
            "cancelled": metrics.counter("serve.cancelled"),
            "watchdog": metrics.counter("serve.watchdog_fires"),
            "row_failed": metrics.counter("serve.row_failures"),
            "recovered": metrics.counter("serve.recovered"),
            "replayed": metrics.counter("serve.replayed_tokens"),
        }

    def set_journal(self, journal) -> None:
        """Attach (or detach, with None) a request :class:`~repro.serve
        .journal.Journal`. Rebindable while the engine is idle — the
        journal-overhead gate toggles the WAL on ONE engine the same way
        :meth:`set_obs` toggles observability. Accepts a path string."""
        if isinstance(journal, str):
            journal = Journal(journal)
        old = self._journal
        self._journal = journal
        if journal is not None and self.obs is not None:
            journal.set_metrics(self.obs.metrics)
        if old is not None and old is not journal:
            old.close()

    def _phase_begin(self, slot: int, name: str, t: float) -> None:
        self._slot_span[slot] = (name, t)

    def _phase_end(self, slot: int, t: float, req=None) -> None:
        cur = self._slot_span[slot]
        self._slot_span[slot] = None
        if cur is not None and self._tr is not None:
            args = {"req": req.id} if req is not None else None
            self._tr.add(cur[0], f"slot{slot}", cur[1], t, args)

    def _note_seated(self, slot: int, req, now: float) -> None:
        """Retroactive lifecycle spans, emitted at seat time (the slot a
        request will occupy is unknown until the decode-stage merge):
        ``queued`` [enqueue -> admission pop], ``admitted`` [pop -> merge],
        then the open ``prefill``/``decode`` phase span. A preempted
        request re-enters here on its NEXT admission, so its track shows
        every queued/admitted/decode re-entry."""
        tr = self._tr
        track = f"slot{slot}"
        adm = req.last_admitted_at or now
        if req.queued_since is not None:
            tr.add("queued", track, req.queued_since, adm,
                   {"req": req.id, "preempted": req.preempted_count})
        tr.add("admitted", track, adm, now, {"req": req.id})
        self._phase_begin(slot, self._slot_phase[slot], now)

    def _note_first_token(self, req, now: float) -> None:
        if req.first_token_at is None:
            req.first_token_at = now
            if self._journal is not None:
                self._journal.first_token(req)
            if self._mh is not None and req.submitted_at is not None:
                ttft = now - req.submitted_at
                self._mh["ttft"].record(ttft)
                h = self._mh_tier.get(req.priority)
                if h is None:
                    h = self.obs.metrics.histogram(
                        f"serve.ttft_s.tier{req.priority}")
                    self._mh_tier[req.priority] = h
                h.record(ttft)

    def _sched_event(self, kind: str, req) -> None:
        """Scheduler sweep callback (outside the scheduler lock): a waiting
        request was dropped — ``kind`` in ``("expired", "cancelled")``."""
        with self._state_lock:
            self.stats[kind] += 1
        if self._journal is not None:
            self._journal.cancel(req, kind)
        if self._mh is not None:
            self._mh[kind].inc()
        if self._tr is not None:
            self._tr.instant(kind, TRACK_ENGINE, time.perf_counter(),
                             {"req": req.id, "state": "waiting"})

    def _note_resident(self) -> None:
        if self._mh is not None:
            self._mh["resident"].set(
                sum(r is not None for r in self._slot_req))

    # ------------------------------------------------------- mesh placement
    def _dev(self, x):
        """Upload a host array REPLICATED over the serve mesh (plain
        ``jnp.asarray`` off-mesh). Used for every device-resident array the
        compiled programs treat as replicated — block tables, the decode
        carry, slot state — so no launch ever sees an unexpectedly
        device-0-committed operand."""
        a = jnp.asarray(x)
        if self._repl_ns is not None:
            a = jax.device_put(a, self._repl_ns)
        return a

    def _place_pool(self, pkv):
        """Commit a freshly built KV pool to its mesh sharding: partitioned
        on the KV-head axis when the model axis shards attention, else
        replicated-equivalent single-device placement. Keeping the pool
        committed is what makes the per-device footprint 1/N and lets the
        donated chunk in/out buffers alias without a relayout."""
        if self._pool_sharded:
            pkv = jax.device_put(
                pkv, NamedSharding(self.ctx.mesh,
                                   serve_pool_spec(self.cfg, self.ctx)))
        elif self._repl_ns is not None:
            pkv = jax.device_put(pkv, self._repl_ns)
        return pkv

    # ---------------------------------------------------------- compiled fns
    def _prefill_impl(self, params, tokens, last_positions, max_len: int):
        with use_shard_ctx(self.ctx):
            return lm.prefill(self.cfg, params, tokens, max_len=max_len,
                              last_positions=last_positions, ctx=self.ctx)

    def _decode_n_impl(self, params, cache, token, n: int):
        """n contiguous decode steps in one XLA launch (per-call baseline)."""
        with use_shard_ctx(self.ctx):
            def body(carry, _):
                cache, tok = carry
                logits, cache = lm.decode_step(self.cfg, params, cache, tok)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (cache, nxt), nxt

            (cache, tok), toks = jax.lax.scan(body, (cache, token),
                                              None, length=n)
            return cache, toks.swapaxes(0, 1)  # (B, n)

    def _decode_paged_impl(self, params, pkv, tables, lengths, last,
                           rem, n: int):
        """One chunk: ``n`` paged decode steps over the resident batch in a
        single XLA launch (:func:`repro.models.lm.decode_chunk_paged` — the
        shared device-carry chunk program; the sync path feeds it uploaded
        host mirrors, the async path feeds it the previous chunk's output
        carry directly). Rows with ``rem == 0`` are inactive: their KV
        writes go to the sink block and their emitted tokens are discarded
        host-side. The attention read path is ``self.paged_impl``.
        Returns the advanced state + (B, n) greedy tokens."""
        with use_shard_ctx(self.ctx):
            pkv, (ln, tok, rm), toks = lm.decode_chunk_paged(
                self.cfg, params, pkv, tables, (lengths, last, rem), n,
                impl=self.paged_impl, ctx=self.ctx)
            return pkv, tok, ln, rm, toks

    def _decode_slots_impl(self, params, state, last, lengths, rem, n: int):
        """One chunk over the SSM/hybrid slot-state pool
        (:func:`repro.models.lm.decode_chunk_slots` at per-row positions).
        Inactive slots step on stale state harmlessly (row-wise math; their
        tokens are discarded host-side and their slot is overwritten at the
        next admission)."""
        with use_shard_ctx(self.ctx):
            st, (ln, tok, rm), toks = lm.decode_chunk_slots(
                self.cfg, params, state, (lengths, last, rem), n,
                ctx=self.ctx)
            return st, tok, ln, rm, toks

    def _prefill_window_impl(self, params, pkv, tables, tokens, start,
                             valid, last_idx):
        with use_shard_ctx(self.ctx):
            return lm.prefill_window_paged(self.cfg, params, pkv, tables,
                                           tokens, start, valid, last_idx,
                                           ctx=self.ctx)

    def _scatter_impl(self, pkv, blocks, krows, vrows):
        return scatter_prefill_rows(pkv, blocks, krows, vrows)

    # ------------------------------------------------------------- lifecycle
    def _ensure_executor(self) -> Executor:
        if self._executor is None:
            self._executor = Executor(domains={HOST: 2, ACCEL: 1})
            self._own_executor = True
        return self._executor

    def _ensure_pipeline(self, ex: Executor) -> DataPipeline:
        if self._pipeline is None:
            decode_domain = ACCEL if ex.has_domain(ACCEL) else HOST
            self._pipeline = DataPipeline(
                self.pipeline_lines,
                DataPipe(PipeType.SERIAL, self._st_admit, name="admit"),
                DataPipe(PipeType.SERIAL, self._st_prefill, name="prefill"),
                DataPipe(PipeType.SERIAL, self._st_decode, name="decode",
                         domain=decode_domain),
                DataPipe(PipeType.PARALLEL, self._st_complete,
                         name="complete"),
                name="serve-continuous")
            # promote stage_times into per-line spans when tracing is on
            self._pipeline.tracer = self._tr
        return self._pipeline

    # --------------------------------------------------------------- watchdog
    def _watchdog_busy(self) -> bool:
        """Lock-free busy probe (container truthiness is atomic enough for
        a heuristic; the watchdog must never block on a lock a wedged stage
        might hold)."""
        return bool(self._inflight) or bool(self._cycle_tokens) \
            or self._scheduler.num_waiting > 0

    def _watchdog_loop(self) -> None:
        """Daemon thread: fail every outstanding future with a diagnostic
        :class:`WatchdogTimeout` when a BUSY engine makes no stage progress
        (heartbeat ``_wd_beat``, touched by every admit/decode/complete
        entry and by ``submit``) for ``watchdog_s`` seconds. The stuck
        device call itself cannot be interrupted — the point is that
        ``result()`` raises a diagnostic instead of hanging forever."""
        period = max(0.01, self._watchdog_s / 4.0)
        while not self._wd_stop.wait(period):
            if self._broken is not None:
                return
            stale = time.perf_counter() - self._wd_beat
            if stale <= self._watchdog_s or not self._watchdog_busy():
                continue
            err = WatchdogTimeout(
                f"engine made no cycle progress for {stale:.3f}s "
                f"(budget {self._watchdog_s:.3f}s; "
                f"inflight={len(self._inflight)} "
                f"waiting={self._scheduler.num_waiting} "
                f"cycles={sorted(self._cycle_tokens)}; a stuck device "
                f"sync or a deadlocked stage — failing all futures)")
            self._broken = err
            with self._state_lock:
                self.stats["watchdog_fires"] += 1
            if self._mh is not None:
                self._mh["watchdog"].inc()
            if self._tr is not None:
                self._tr.instant("watchdog_fire", TRACK_ENGINE,
                                 time.perf_counter(), {"stale_s": stale})
            self._fail_outstanding(err)
            return

    def close(self, timeout: float = 300.0) -> None:
        """Drain outstanding requests, then release the executor. Anything
        still outstanding after the drain budget (or after a breakage)
        fails typed :class:`EngineClosed` — ``result()`` never hangs on a
        torn-down engine. Idempotent."""
        self._closing = True
        if self._pipeline is not None:
            deadline = time.perf_counter() + timeout
            while time.perf_counter() < deadline:
                if self._broken is not None:
                    break
                if self._pipeline.idle() and \
                        (self._scheduler.num_waiting == 0
                         or self._draining):
                    # a draining engine never admits its backlog — stop
                    # waiting on it; the typed fail below settles it
                    break
                time.sleep(0.005)
        self._wd_stop.set()
        if self._wd_thread is not None:
            self._wd_thread.join(timeout=1.0)
            self._wd_thread = None
        if self._watchdog_busy():
            # drain gave up (or the pipeline broke): propagate a typed
            # error into every pending future instead of letting result()
            # time out slot by slot
            self._fail_outstanding(EngineClosed(
                "engine closed with requests outstanding "
                "(drain timeout or prior failure)"))
        if self.paged and self._pending is None:
            # drained: no chunk in flight, every deferred block is past the
            # device work that fenced it — flush the fence
            while self._pool.num_deferred:
                self._pool.release_deferred()
        if self._journal is not None:
            self._journal.close()
        if self._own_executor and self._executor is not None:
            self._executor.shutdown()
            self._executor = None
            self._own_executor = False

    def __enter__(self) -> "ServeEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -------------------------------------------------- durability (drain /
    # snapshot / restore / recover — see docs/robustness.md)
    def drain(self, deadline_s: Optional[float] = None,
              timeout: float = 300.0) -> bool:
        """Gracefully drain the engine: stop admitting (``submit()`` and
        the admission stage both gate typed), let resident rows run to
        completion, and — once ``deadline_s`` elapses — have the decode
        stage CHECKPOINT-PREEMPT every remaining resident (SSM sync rows
        capture their exact recurrent state; paged/async rows replay
        bit-identically later) so the engine settles instead of riding
        out its longest resident. The drain thread only sets flags and
        polls: all slot-state mutation stays on the SERIAL decode stage,
        the single writer. Flushes the journal once settled. Returns
        True when the engine reached idle within ``timeout``; waiting
        and preempted requests stay queued — snapshot them, then
        ``close()`` fails them typed :class:`EngineClosed`. Idempotent."""
        self._draining = True
        if deadline_s is not None and self._drain_deadline_at is None:
            self._drain_deadline_at = time.perf_counter() + deadline_s
        settled = True
        if self._pipeline is not None:
            limit = time.perf_counter() + timeout
            settled = False
            while time.perf_counter() < limit:
                if self._broken is not None:
                    settled = True
                    break
                with self._state_lock:
                    occupied = any(r is not None for r in self._slot_req)
                    reserved = self._slots_reserved
                if self._pipeline.idle() and not occupied \
                        and reserved == 0:
                    settled = True
                    break
                time.sleep(0.005)
        if self._journal is not None:
            self._journal.flush()
        return settled

    def snapshot(self, path: str) -> int:
        """Serialize warm state to ``path`` (atomic, checksummed — see
        :mod:`repro.serve.snapshot`): the prefix trie with its stable
        blake2b chunk keys and every indexed block's KV page, plus
        waiting-queue request descriptors. Call at idle (typically right
        after :meth:`drain`): resident rows are NOT captured — the
        journal covers them by replay. Returns bytes written. The
        ``snapshot_corrupt`` fault site flips a payload byte right after
        the write, for the typed cold-fallback tests."""
        if self._journal is not None:
            self._journal.flush()
        meta: Dict[str, Any] = {"paged": self.paged}
        arrays: Dict[str, np.ndarray] = {}
        qdesc = []
        qtoks: List[np.ndarray] = []
        for r in self._scheduler.export_waiting():
            qdesc.append({"id": int(r.id), "max_new": int(r.max_new),
                          "priority": int(r.priority),
                          "deadline_s": r.deadline_s})
            qtoks.append(np.asarray(r.prompt, np.int32))
        meta["queue"] = qdesc
        arrays["queue_tokens"] = (np.concatenate(qtoks) if qtoks
                                  else np.zeros((0,), np.int32))
        arrays["queue_lens"] = np.asarray([len(t) for t in qtoks],
                                          np.int32)
        if self.paged:
            meta["block_size"] = int(self._pool.block_size)
        if self._prefix is not None:
            nodes = self._prefix.export_nodes()
            meta["prefix"] = [{"parent": n["parent"], "key": n["key"],
                               "depth": n["depth"], "hits": n["hits"]}
                              for n in nodes]
            ptoks = [n["tokens"].astype(np.int32) for n in nodes]
            arrays["prefix_tokens"] = (np.concatenate(ptoks) if ptoks
                                       else np.zeros((0,), np.int32))
            arrays["prefix_lens"] = np.asarray(
                [len(t) for t in ptoks], np.int32)
            ids = [n["block"] for n in nodes]
            hp = np.asarray(jax.device_get(self._pkv))
            # (N, L, 2, R, bs, W): page i on axis 0 is node i's block.
            # Stored as RAW BYTES (uint8 view): npz round-trips bfloat16
            # only as opaque void, so the restore side re-views with the
            # live pool dtype (recorded below for the compat check)
            pg = np.ascontiguousarray(hp[ids] if ids else hp[:0])
            arrays["prefix_pages"] = pg.view(np.uint8)
            meta["pool_dtype"] = str(hp.dtype)
        n = write_snapshot(path, meta, arrays)
        if self._fi is not None and self._fi.fire("snapshot_corrupt"):
            corrupt_snapshot(path)
        return n

    def restore(self, path: str) -> List[Dict[str, Any]]:
        """Warm-start THIS (freshly constructed, idle) engine from a
        :meth:`snapshot` file: rebuild the prefix trie — fresh pool
        blocks are allocated, the saved KV pages written into them, and
        the nodes adopted PARKED and flagged warm, so a known system
        prompt hits the cache on the first post-restart request
        (``prefix.warm_hits``) — and return the waiting-queue
        descriptors for the caller (:meth:`recover` re-submits them when
        no journal supersedes the snapshot). Raises typed
        :class:`SnapshotCorrupt` on any integrity or geometry mismatch
        BEFORE mutating engine state, so callers fall back to a cold
        start: a snapshot can lose warmth, never serve wrong tokens."""
        meta, arrays = read_snapshot(path)
        if bool(meta.get("paged")) != self.paged:
            raise SnapshotCorrupt(
                f"snapshot arch mismatch: paged={meta.get('paged')} vs "
                f"engine paged={self.paged}")
        queue: List[Dict[str, Any]] = []
        qlens = arrays.get("queue_lens")
        qtoks = arrays.get("queue_tokens")
        if qlens is not None and qtoks is not None:
            off = 0
            for d, ln in zip(meta.get("queue", []),
                             [int(x) for x in qlens]):
                d = dict(d)
                d["prompt"] = np.asarray(qtoks[off:off + ln], np.int32)
                off += ln
                queue.append(d)
        entries = meta.get("prefix") or []
        if entries and self._prefix is not None:
            pages = arrays["prefix_pages"]
            plens = [int(x) for x in arrays["prefix_lens"]]
            # np.array (not asarray): device views are read-only and the
            # page import writes into this host copy before re-upload
            hp = np.array(jax.device_get(self._pkv))
            if meta.get("pool_dtype") != str(hp.dtype):
                raise SnapshotCorrupt(
                    f"snapshot pool dtype mismatch: "
                    f"{meta.get('pool_dtype')!r} vs engine {hp.dtype}")
            pages = pages.view(hp.dtype)     # stored as raw uint8 bytes
            want = (pages.shape[0],) + hp.shape[1:]
            if int(meta.get("block_size", -1)) != self._pool.block_size \
                    or pages.shape != want:
                raise SnapshotCorrupt(
                    f"snapshot pool geometry mismatch: pages "
                    f"{pages.shape} / block_size "
                    f"{meta.get('block_size')} vs engine "
                    f"{want} / {self._pool.block_size}")
            off, toks = 0, []
            for ln in plens:
                toks.append(np.asarray(
                    arrays["prefix_tokens"][off:off + ln], np.int32))
                off += ln
            for e, t in zip(entries, toks):
                e["tokens"] = t
            # leave headroom: warmth must never consume the whole pool
            n = min(len(entries),
                    max(0, self._pool.num_free_unreserved - 1))
            ids = self._pool.alloc(n) if n > 0 else []
            if ids:
                hp[ids] = pages[:len(ids)]
                self._pkv = self._place_pool(jnp.asarray(hp))
                created = self._prefix.import_nodes(entries[:len(ids)],
                                                    ids)
                with self._state_lock:
                    self.stats["warm_started"] += created
        return queue

    def recover(self, state_dir: str, *, fsync_every: int = 1
                ) -> Dict[int, ServeRequest]:
        """Crash/restart recovery against a ``--state-dir``: restore the
        snapshot if one exists (typed :class:`SnapshotCorrupt` falls
        back to a cold start — warmth lost, correctness kept), replay
        the journal and RE-SUBMIT every incomplete request (greedy
        decode makes the replay bit-identical; deadlines re-arm in
        full), rotate the consumed journal aside and attach a fresh one
        at the same path. The snapshot's queue descriptors are used only
        when no journal exists — with one, its submit records are a
        superset. Returns ``{old request id: new future}`` so the
        caller can hand back or verify the replayed results."""
        os.makedirs(state_dir, exist_ok=True)
        spath = os.path.join(state_dir, SNAPSHOT_FILE)
        jpath = os.path.join(state_dir, JOURNAL_FILE)
        queue: List[Dict[str, Any]] = []
        if os.path.exists(spath):
            try:
                queue = self.restore(spath)
            except SnapshotCorrupt:
                queue = []    # cold start; the journal still replays
        rep = replay_journal(jpath)
        pending = rep.incomplete if rep.submits else queue
        if os.path.exists(jpath):
            os.replace(jpath, jpath + ".replayed")
        self.set_journal(Journal(jpath, fsync_every=fsync_every))
        out: Dict[int, ServeRequest] = {}
        ntok = 0
        for rec in pending:
            prompt = np.asarray(rec["prompt"], np.int32)
            req = self.submit(prompt, int(rec["max_new"]),
                              priority=int(rec.get("priority", 0)),
                              deadline_s=rec.get("deadline_s"))
            out[int(rec["id"])] = req
            ntok += len(prompt)
        with self._state_lock:
            self.stats["recovered"] += len(out)
            self.stats["replayed_tokens"] += ntok
        if self._mh is not None and out:
            self._mh["recovered"].inc(len(out))
            self._mh["replayed"].inc(ntok)
        return out

    # ------------------------------------------------------- stage callables
    def _log(self, stage: str, token: int, info: Any) -> None:
        if self._stage_log is not None:
            with self._log_lock:
                self._stage_log.append((stage, token, info,
                                        time.perf_counter()))

    @property
    def stage_log(self) -> List[tuple]:
        """(stage, cycle-token, info, timestamp) events (record_stages=True)."""
        with self._log_lock:
            return list(self._stage_log or [])

    def _st_admit(self, pf):
        with span("serve.admission", self._tr) as sp:
            return self._admit(pf, sp)

    def _admit(self, pf, sp):
        self._wd_beat = sp.t0
        epoch = self._reset_epoch
        with self._state_lock:
            occupied = any(r is not None for r in self._slot_req)
            reserved = self._slots_reserved
            deps = set(self._cycle_tokens)
            free_slots = len(self._free_slots) - reserved
        waiting = self._scheduler.num_waiting
        draining = self._draining
        if draining:
            # graceful drain: admission is closed. Residents keep decoding
            # via pump cycles below; anything still waiting is failed typed
            # by close() after the drain settles. Forcing `waiting` to 0
            # here lets the idle-stop fire the moment the last resident
            # retires even with a backlog queued behind the gate.
            waiting = 0
        if not waiting and not occupied and reserved == 0:
            # fully idle — nothing queued, no live rows, and no admitted
            # group still in flight toward its decode merge: drain so the
            # engine parks at zero cost; the next submit() re-arms the SAME
            # resident grid (no rebuild)
            pf.stop()
            return None
        group = None
        if draining:
            popped = None
        elif self.paged:
            # phase 1 of two-phase admission: budget the PROMPT footprint
            # only — minus any prompt blocks the prefix cache already holds
            # (peek is conservative: registration can only grow a match
            # between the peek and the pin below) — and count PARKED cached
            # blocks toward the budget, since they are evictable on demand;
            # decode-time blocks are granted lazily by the decode stage.
            # The budget sees free blocks MINUS the stalled-row reservation
            # floor: a stalled resident row is starving for blocks that are
            # (or will be) released by the deferred-free fence, and handing
            # them to a new request would make the grow pass preempt that
            # request right back — an admit/preempt livelock. The grow pass
            # reserves each stalled row's unmet demand
            # (:meth:`BlockPool.set_reserved`, drained oldest-stalled-first
            # by the grow pass's age order), so admission proceeds on the
            # surplus instead of halting outright while anything is stalled.
            px = self._prefix
            if px is not None:
                bs = self._pool.block_size

                def need_for(r):
                    return self._pool.blocks_for(r.prompt_len) \
                        - px.peek(r.prompt) // bs
                budget = self._pool.num_free_unreserved + px.num_parked
            else:
                def need_for(r):
                    return self._pool.blocks_for(r.prompt_len)
                budget = self._pool.num_free_unreserved
            popped = self._scheduler.try_admit(free_slots, budget, need_for,
                                               hopeless=self._hopeless_why)
            if popped is not None:
                # pin the longest cached prefix per member (ref++ on every
                # matched block) and allocate only the uncached suffixes
                hits = [px.match_and_pin(r.prompt) if px is not None
                        else None for r in popped]
                needs = [self._pool.blocks_for(r.prompt_len)
                         - (len(h.blocks) if h is not None else 0)
                         for r, h in zip(popped, hits)]
                if self._fi is not None and self._fi.fire("alloc_fail"):
                    ids = None          # injected admission-alloc failure
                else:
                    ids = self._pool.alloc(sum(needs))  # all-or-nothing
                if ids is None and px is not None:
                    # reuse-aware back-pressure: release cold PARKED prefix
                    # blocks (leaf-first, coldest score first) before giving
                    # up on the group — and long before the grow pass would
                    # preempt any resident row
                    short = sum(needs) - self._pool.num_free_unreserved
                    if short > 0:
                        px.evict(short)
                    ids = self._pool.alloc(sum(needs))
                if ids is None:
                    # raced a concurrent mid-decode grow: unpin, put the
                    # group back (id order preserved), fall through to
                    # park/pump
                    for h in hits:
                        if h is None:
                            continue
                        pins = list(h.blocks)
                        if h.partial_block is not None:
                            pins.append(h.partial_block)
                        if pins:
                            px.unpin(pins)
                    self._scheduler.requeue_front(popped)
                else:
                    group, i, saved, nhit = [], 0, 0, 0
                    for r, h, need in zip(popped, hits, needs):
                        group.append((r, ids[i:i + need], h))
                        i += need
                        if h is not None and h.tokens > 0:
                            nhit += 1
                            saved += h.tokens
                    if nhit:
                        with self._state_lock:
                            self.stats["prefix_hits"] += nhit
                            self.stats["prefix_tokens_saved"] += saved
                        if self._mh is not None:
                            self._mh["prefill_saved"].inc(saved)
        else:
            # slot-state pool: recurrent state is pre-allocated per slot, so
            # admission is bounded by free slots alone
            popped = self._scheduler.try_admit(free_slots, None,
                                               hopeless=self._hopeless_why)
            if popped is not None:
                group = [(r, None) for r in popped]
        if group is not None:
            now = time.perf_counter()
            for g in group:
                r = g[0]
                r.state = "prefilling"
                if r.admitted_at is None:
                    r.admitted_at = now
                    if self._mh is not None and r.submitted_at is not None:
                        self._mh["qwait"].record(now - r.submitted_at)
            with self._state_lock:
                stale = epoch != self._reset_epoch
                if not stale:
                    self._slots_reserved += len(group)
                    self._inflight.update(g[0] for g in group)
                    self._cycle_tokens.add(pf.token)
                    self._premerge[pf.token] = (epoch,
                                                [g[0] for g in group])
                    self.stats["admitted"] += len(group)
            if stale:
                # a failure-isolation reset raced this admission: the block
                # ids above came from the pre-reset pool and are dead. Fail
                # the group typed (re-submit is safe and deterministic)
                # instead of seating it on a fresh pool it never allocated
                # from.
                err = RowFailed(
                    "admission raced an engine failure-isolation reset")
                for g in group:
                    g[0].set_error(err)
                return ("pump", None)
            if self._journal is not None:
                for g in group:
                    self._journal.admit(g[0])
            if self._mh is not None:
                self._mh["admitted"].inc(len(group))
            sp.annotate(req=[g[0].id for g in group])
            self._log("admit", pf.token, [g[0].id for g in group])
            return ("admit", group)
        if waiting and deps:
            # deferred-token admission: the head request does not fit. Park
            # THIS cycle until the oldest in-flight cycle fully completes
            # (its complete stage frees retired blocks), instead of spinning
            # empty admissions; the in-flight cycles keep the decode pump
            # alive meanwhile.
            dep = min(deps)
            with self._state_lock:
                self.stats["admit_parks"] += 1
            self._log("park", pf.token, dep)
            pf.defer(dep)
            return None
        # nothing admittable but sequences are running (or their retirement
        # is still in flight): emit a pure decode-pump cycle
        with self._state_lock:
            self._cycle_tokens.add(pf.token)
            self.stats["pump_cycles"] += 1
        sp.annotate(pump=True)
        self._log("pump", pf.token, None)
        return ("pump", None)

    def _st_prefill(self, pf, msg):
        kind, payload = msg
        if kind != "admit":
            return msg
        try:
            with span("serve.prefill", self._tr,
                      req=[g[0].id for g in payload]):
                return self._prefill_group(pf, payload)
        except Exception as exc:           # per-group failure isolation
            return self._prefill_failed(pf, payload, exc)

    def _prefill_failed(self, pf, group, exc):
        """A raising prefill launch fails ONLY the admitted group (typed
        :class:`RowFailed`), releases its untouched resources, and the
        engine keeps serving — prefill never donates the KV pool, so no
        device-state reset is needed (contrast :meth:`_isolate_failure`)."""
        err = RowFailed(
            f"prefill launch failed for group "
            f"{[g[0].id for g in group]}: {exc!r}")
        err.__cause__ = exc
        with self._state_lock:
            info = self._premerge.pop(pf.token, None)
            live = info is not None and info[0] == self._reset_epoch
            if live:
                self._slots_reserved -= len(group)
                for g in group:
                    self._inflight.discard(g[0])
            self.stats["row_failures"] += len(group)
        if live and self.paged:
            for g in group:
                blocks, hit = g[1], g[2]
                if blocks:
                    # allocated at admit, never scattered: no device work
                    # references them, a plain free is safe even in async
                    self._pool.free(list(blocks))
                if hit is not None:
                    pins = list(hit.blocks)
                    if hit.partial_block is not None:
                        pins.append(hit.partial_block)
                    if pins:
                        self._prefix.unpin(pins)
        for g in group:
            g[0].set_error(err)
        if self._mh is not None:
            self._mh["row_failed"].inc(len(group))
        if self._tr is not None:
            self._tr.instant("prefill_failed", TRACK_ENGINE,
                             time.perf_counter(),
                             {"reqs": [g[0].id for g in group]})
        self._log("prefill_failed", pf.token, [g[0].id for g in group])
        return ("pump", None)

    def _prefill_group(self, pf, group):
        reqs = [g[0] for g in group]
        if not self.paged:
            # SSM/hybrid: whole-prompt prefill per member (recurrent state
            # is O(1)/sequence — there is no per-token KV to chunk in; the
            # compiled shape keys on each prompt length, as the grouped
            # baseline's did)
            out = []
            n_pref = 0
            for req in reqs:
                if getattr(req, "_ssm_ckpt", None) is not None:
                    # checkpoint-preempted row (drain deadline / boost in
                    # sync mode): the exact recurrent state was captured at
                    # preemption — re-seat it directly, no prefill replay
                    out.append((req, None, None))
                    continue
                logits, cache = self._prefill(
                    self.params, jnp.asarray(req.prompt[None]), None,
                    max_len=req.prompt_len)
                first = int(np.asarray(jnp.argmax(logits, axis=-1))[0])
                out.append((req, cache, first))
                n_pref += 1
            with self._state_lock:
                self.stats["prefills"] += n_pref
            self._log("prefill", pf.token, [r.id for r in reqs])
            return ("admit", out)
        # one launch for the group's FIRST prompt window: prompts are
        # right-padded to a single window shape (chunked prefill keys the
        # compiled program on the window size, never on prompt lengths, so
        # mixed-length groups ride together; pad rows repeat the last
        # request and scatter to the sink). Remaining windows stream through
        # the decode stage cycle by cycle. The window is rounded up to a
        # power of two (capped at prefill_chunk) so arbitrary prompt-length
        # mixes compile O(log prefill_chunk) shapes, not one per length.
        # Prefix-cache HIT rows skip this launch entirely: their cached
        # tokens never re-prefill — the decode stage seats them with the
        # shared blocks and streams windows from the first uncached token
        # (the group is reordered miss-first so launch row i is group
        # member i for every window-0 participant).
        miss = [g for g in group if g[2] is None or g[2].tokens == 0]
        hitg = [g for g in group if not (g[2] is None or g[2].tokens == 0)]
        group = miss + hitg
        if not miss:
            self._log("prefill", pf.token, [r.id for r in reqs])
            return ("admit", (group, 0, None, None, None, 0))
        longest = max(g[0].prompt_len for g in miss)
        C0 = min(self.prefill_chunk, 1 << max(0, longest - 1).bit_length())
        A = self._scheduler.max_admit
        toks = np.zeros((A, C0), np.int32)
        lastp = np.zeros((A,), np.int32)
        for i, g in enumerate(miss):
            r = g[0]
            k = min(r.prompt_len, C0)
            toks[i, :k] = r.prompt[:k]
            lastp[i] = k - 1
        for i in range(len(miss), A):
            toks[i] = toks[len(miss) - 1]
            lastp[i] = lastp[len(miss) - 1]
        logits, cache = self._prefill(self.params, jnp.asarray(toks),
                                      jnp.asarray(lastp), max_len=C0)
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        with self._state_lock:
            self.stats["prefills"] += 1
        self._log("prefill", pf.token, [r.id for r in reqs])
        return ("admit", (group, C0, cache["k"], cache["v"], first,
                          len(miss)))

    # ------------------------------------------------- decode-stage helpers
    def _scatter_carry(self, rows, lens, lasts, rems, pad_to: int) -> None:
        """Fixed-shape scatter onto the device-resident carry: pad every
        list with repeats of its last element (duplicate writes of the same
        row are idempotent) so each call site compiles exactly ONE shape
        regardless of how many rows it touches. Async mode only — the sync
        path re-uploads the host mirrors each cycle instead."""
        rows, lens = list(rows), list(lens)
        lasts, rems = list(lasts), list(rems)
        while len(rows) < pad_to:
            rows.append(rows[-1])
            lens.append(lens[-1])
            lasts.append(lasts[-1])
            rems.append(rems[-1])
        self._carry = self._set_carry(
            *self._carry, jnp.asarray(rows, jnp.int32),
            jnp.asarray(lens, jnp.int32), jnp.asarray(lasts, jnp.int32),
            jnp.asarray(rems, jnp.int32))

    def _premerge_live(self, pf, n: int) -> bool:
        """Epoch guard at the decode-stage merge: an admitted group that
        predates a failure-isolation reset must NOT seat — its block ids
        came from the torn-down pool, and its requests were already failed
        by the reset. PEEKS (the record is popped at the END of a merge, so
        a crash mid-merge still finds every group member in the pre-merge
        table and fails it — double ``set_error`` is a no-op)."""
        with self._state_lock:
            info = self._premerge.get(pf.token)
            if info is None or info[0] != self._reset_epoch:
                return False
        return True

    def _merge_group(self, pf, payload) -> None:
        """Seat an admitted group: assign slots, install block tables, and
        scatter the window-0 KV into the pool (single-writer: we are inside
        the SERIAL decode stage). Rows whose whole prompt fits window 0
        enter decode immediately; longer ones enter the prefill phase and
        stream their remaining windows in subsequent cycles.

        Prefix-cache HIT rows (group members past ``n_miss`` — they took no
        window-0 launch row) seed their table with the pinned SHARED prefix
        blocks followed by their own suffix blocks and enter the prefill
        phase at the first uncached token; a partially-matched tail block
        is copy-on-write FORKED here (device block copy into the row's
        first suffix block, which the table already points at) so the
        row's own writes never touch the shared original."""
        group, C0, ck, cv, first, n_miss = payload
        if not self._premerge_live(pf, len(group)):
            return
        first = np.asarray(first) if first is not None else None
        nb0 = self._pool.blocks_for(C0) if C0 else 0
        now = time.perf_counter()
        rows_idx, rows_tab = [], []
        c_len, c_last, c_rem = [], [], []
        fork_src, fork_dst = [], []
        reg_slots = []
        for i, (req, blocks, hit) in enumerate(group):
            shared = list(hit.blocks) if (hit is not None and i >= n_miss) \
                else []
            tab = shared + list(blocks)
            with self._state_lock:
                slot = self._free_slots.pop()
                self._slots_reserved -= 1
                self._slot_req[slot] = req
                self._slot_blocks[slot] = tab
                self._slot_out[slot] = []
            self._slot_gen[slot] += 1
            self._slot_prompt[slot] = req.prompt
            self._wp_valid[slot] = False
            self._stall_rem[slot] = 0
            self._tables[slot] = 0
            self._tables[slot, :len(tab)] = tab
            if shared or (hit is not None and i >= n_miss):
                # cache hit: cached tokens are already in the pool — start
                # the window walk at the first uncached token
                self._pref_pos[slot] = hit.tokens
                if hit.partial_block is not None:
                    # CoW fork of the partially-matched tail block into the
                    # row's first suffix block (table column len(shared)):
                    # its cached leading tokens come along, the row's own
                    # writes land past them
                    fork_src.append(hit.partial_block)
                    fork_dst.append(blocks[0])
                    with self._state_lock:
                        self.stats["cow_forks"] += 1
                    if self._tr is not None:
                        self._tr.instant(
                            "cow_fork", f"slot{slot}", now,
                            {"req": req.id, "src": int(hit.partial_block),
                             "dst": int(blocks[0])})
            else:
                self._pref_pos[slot] = min(req.prompt_len, C0)
            self._lengths[slot] = self._pref_pos[slot]
            if i < n_miss and req.prompt_len <= C0:
                self._slot_phase[slot] = "decode"
                self._last[slot] = first[i]
                self._rem[slot] = req.max_new - 1
                self._slot_out[slot].append(int(first[i]))
                req.state = "decoding"
                self._note_first_token(req, now)
                reg_slots.append(slot)
            else:
                self._slot_phase[slot] = "prefill"
                self._last[slot] = 0
                self._rem[slot] = 0   # masked out of decode until prefilled
            if self._tr is not None:
                self._note_seated(slot, req, now)
            rows_idx.append(slot)
            rows_tab.append(self._tables[slot].copy())
            c_len.append(int(self._lengths[slot]))
            c_last.append(int(self._last[slot]))
            c_rem.append(int(self._rem[slot]))
        # pad the row-set scatters to the admission cap (duplicate writes of
        # the same row are idempotent): ONE compiled shape per engine, not
        # one per group size
        A = self._scheduler.max_admit
        while len(rows_idx) < A:
            rows_idx.append(rows_idx[-1])
            rows_tab.append(rows_tab[-1])
        self._tables_dev = self._set_rows(
            self._tables_dev, jnp.asarray(rows_idx, jnp.int32),
            jnp.asarray(np.stack(rows_tab)))
        if self.async_decode:
            # admission scatter onto the device carry, sequenced BEFORE the
            # next chunk dispatch: the seated rows were inactive (rem==0) in
            # the chunk still in flight, so scattering onto its output carry
            # is exact
            self._scatter_carry(rows_idx[:len(group)], c_len, c_last, c_rem,
                                pad_to=A)
        if fork_src:
            # partial-tail forks: one padded device copy for the whole
            # group, sequenced on the pool chain before any window launch
            # that reads the forked blocks
            self._copy_blocks_padded(fork_src, fork_dst)
            self._prefix.unpin(fork_src)   # fork done: drop the tail pins
        if n_miss:
            # window-0 scatter: per-row block lists trimmed/padded to the
            # window footprint (sink-filled beyond a short prompt's own
            # blocks and for the group's pad rows), so the compiled shape
            # keys on the window size alone — never on group size, prompt
            # lengths, or max_new
            blocks2d = np.zeros((ck.shape[1], nb0), np.int32)
            for i, (_, blocks, _) in enumerate(group[:n_miss]):
                row = blocks[:nb0]
                blocks2d[i, :len(row)] = row
            self._pkv = self._scatter(self._pkv, jnp.asarray(blocks2d),
                                      ck, cv)
        for slot in reg_slots:
            self._register_prefix(slot)
        with self._state_lock:
            self._premerge.pop(pf.token, None)   # fully seated
        self._note_resident()

    def _copy_blocks_padded(self, srcs: List[int], dsts: List[int]) -> None:
        """One :func:`repro.serve.kvcache.copy_blocks` launch, padded with
        ``SINK -> SINK`` repeats to the next power of two so arbitrary fork
        counts compile O(log max_batch) shapes."""
        m = 1 << max(0, len(srcs) - 1).bit_length()
        srcs = list(srcs) + [SINK_BLOCK] * (m - len(srcs))
        dsts = list(dsts) + [SINK_BLOCK] * (m - len(dsts))
        self._pkv = self._cow_copy(self._pkv, jnp.asarray(srcs, jnp.int32),
                                   jnp.asarray(dsts, jnp.int32))

    def _register_prefix(self, slot: int) -> None:
        """Index a just-prefilled row's FULL prompt chunks in the prefix
        trie (decode entry is the registration point: every full prompt
        block is final — decode writes land strictly past the prompt)."""
        if self._prefix is None:
            return
        prompt = self._slot_prompt[slot]
        blocks = self._slot_blocks[slot]
        if prompt is not None and blocks is not None:
            self._prefix.register(prompt, blocks)

    def _merge_group_slots(self, pf, payload) -> None:
        """Seat an admitted SSM/hybrid group: scatter each member's
        prefilled recurrent state (and zamba2 shared-KV span) into its
        slot of the fixed-slot state pool."""
        if not self._premerge_live(pf, len(payload)):
            return
        now = time.perf_counter()
        rows_idx, c_len, c_last, c_rem = [], [], [], []
        for req, cache, first in payload:
            ckpt = getattr(req, "_ssm_ckpt", None)
            with self._state_lock:
                slot = self._free_slots.pop()
                self._slots_reserved -= 1
                self._slot_req[slot] = req
                self._slot_phase[slot] = "decode"
            self._slot_gen[slot] += 1
            if ckpt is not None:
                # checkpoint-preempted row: re-seat the exact recurrent
                # state captured at preemption and resume mid-stream —
                # no prefill, no token re-emission (out already holds
                # everything emitted before the preemption)
                state, length, last, rem, out = ckpt
                req._ssm_ckpt = None
                self._restore_slot_state(slot, state)
                self._slot_out[slot] = list(out)
                self._lengths[slot] = length
                self._last[slot] = last
                self._rem[slot] = rem
            else:
                self._write_slot_state(slot, cache, req.prompt_len)
                self._slot_out[slot] = [first]
                self._lengths[slot] = req.prompt_len
                self._last[slot] = first
                self._rem[slot] = req.max_new - 1
                self._note_first_token(req, now)
            req.state = "decoding"
            if self._tr is not None:
                self._note_seated(slot, req, now)
            rows_idx.append(slot)
            c_len.append(int(self._lengths[slot]))
            c_last.append(int(self._last[slot]))
            c_rem.append(int(self._rem[slot]))
        if self.async_decode:
            self._scatter_carry(rows_idx, c_len, c_last, c_rem,
                                pad_to=self._scheduler.max_admit)
        with self._state_lock:
            self._premerge.pop(pf.token, None)   # fully seated
        self._note_resident()

    def _write_slot_state(self, slot: int, cache, plen: int) -> None:
        cfg = self.cfg
        if cfg.hybrid_attn_every:
            conv, h = cache["g_ssm"]
            sc, sh = self._sstate["g_ssm"]
            self._sstate["g_ssm"] = (sc.at[:, :, slot].set(conv[:, :, 0]),
                                     sh.at[:, :, slot].set(h[:, :, 0]))
            if "tail_ssm" in self._sstate:
                tconv, th = cache["tail_ssm"]
                stc, sth = self._sstate["tail_ssm"]
                self._sstate["tail_ssm"] = (stc.at[:, slot].set(tconv[:, 0]),
                                            sth.at[:, slot].set(th[:, 0]))
            self._sstate["shared_k"] = self._sstate["shared_k"] \
                .at[:, slot, :, :plen].set(cache["shared_k"][:, 0])
            self._sstate["shared_v"] = self._sstate["shared_v"] \
                .at[:, slot, :, :plen].set(cache["shared_v"][:, 0])
        else:
            conv, h = cache["ssm"]
            sc, sh = self._sstate["ssm"]
            self._sstate["ssm"] = (sc.at[:, slot].set(conv[:, 0]),
                                   sh.at[:, slot].set(h[:, 0]))

    def _save_slot_state(self, slot: int) -> Dict[str, Any]:
        """Capture one slot's recurrent state (and zamba2 shared-KV span)
        to HOST memory — the SSM/hybrid checkpoint-preemption path.
        Sliced copies, not aliases: the donated ``_sstate`` buffers can be
        consumed by the next chunk without invalidating the checkpoint.
        Sync mode only — async's in-flight chunk has already advanced the
        device state past the host mirrors, so its preemptions replay
        from the prompt instead (bit-identical either way)."""
        g = jax.device_get
        st: Dict[str, Any] = {}
        if self.cfg.hybrid_attn_every:
            sc, sh = self._sstate["g_ssm"]
            st["g_ssm"] = (g(sc[:, :, slot]), g(sh[:, :, slot]))
            if "tail_ssm" in self._sstate:
                stc, sth = self._sstate["tail_ssm"]
                st["tail_ssm"] = (g(stc[:, slot]), g(sth[:, slot]))
            st["shared_k"] = g(self._sstate["shared_k"][:, slot])
            st["shared_v"] = g(self._sstate["shared_v"][:, slot])
        else:
            sc, sh = self._sstate["ssm"]
            st["ssm"] = (g(sc[:, slot]), g(sh[:, slot]))
        return st

    def _restore_slot_state(self, slot: int, st: Dict[str, Any]) -> None:
        """Scatter a :meth:`_save_slot_state` checkpoint back into a
        (possibly different) slot of the fixed-slot state pool."""
        if self.cfg.hybrid_attn_every:
            conv, h = st["g_ssm"]
            sc, sh = self._sstate["g_ssm"]
            self._sstate["g_ssm"] = (
                sc.at[:, :, slot].set(jnp.asarray(conv)),
                sh.at[:, :, slot].set(jnp.asarray(h)))
            if "tail_ssm" in st:
                tconv, th = st["tail_ssm"]
                stc, sth = self._sstate["tail_ssm"]
                self._sstate["tail_ssm"] = (
                    stc.at[:, slot].set(jnp.asarray(tconv)),
                    sth.at[:, slot].set(jnp.asarray(th)))
            self._sstate["shared_k"] = self._sstate["shared_k"] \
                .at[:, slot].set(jnp.asarray(st["shared_k"]))
            self._sstate["shared_v"] = self._sstate["shared_v"] \
                .at[:, slot].set(jnp.asarray(st["shared_v"]))
        else:
            conv, h = st["ssm"]
            sc, sh = self._sstate["ssm"]
            self._sstate["ssm"] = (sc.at[:, slot].set(jnp.asarray(conv)),
                                   sh.at[:, slot].set(jnp.asarray(h)))

    def _window_prefill_step(self, pf) -> None:
        """Synchronous chunked prefill: build, launch and complete ONE
        prefill window for every mid-prefill row in the same cycle. The
        async path instead calls :meth:`_dispatch_window_prefill` directly
        and completes the window next cycle (:meth:`_finish_window`), so
        reading its first-token logits never blocks behind the in-flight
        decode chunk."""
        pend = self._dispatch_window_prefill(pf)
        if pend is not None:
            self._finish_window(pend)

    def _dispatch_window_prefill(self, pf) -> Optional[Dict[str, Any]]:
        """Launch ONE prefill window for every mid-prefill row: the window's
        KV is computed against the row's paged prefix and scattered straight
        into the pool (one fixed-shape launch however many rows are
        prefilling — resident rows keep decoding in the same cycle). Only
        the prefilling rows are written into the preallocated window
        buffers; everyone else's ``valid`` entries are invariantly False.
        Returns the pending-window descriptor (or None if no row is
        prefilling); completion is :meth:`_finish_window`."""
        B = len(self._slot_req)
        pref = [b for b in range(B) if self._slot_phase[b] == "prefill"]
        if not pref:
            return None
        C = self.prefill_chunk
        toks, valid = self._wp_toks, self._wp_valid
        start, last_idx = self._wp_start, self._wp_last_idx
        ks = {}
        with span("serve.prefill_window", self._tr,
                  req=[self._slot_req[b].id for b in pref]):
            for b in pref:
                prompt = self._slot_prompt[b]
                s = int(self._pref_pos[b])
                k = min(C, len(prompt) - s)
                toks[b, :k] = prompt[s:s + k]
                valid[b, :k] = True
                valid[b, k:] = False
                start[b] = s
                last_idx[b] = min(len(prompt) - 1 - s, C - 1)
                ks[b] = k
            first, pkv = self._prefill_window(
                self.params, self._pkv, self._tables_dev, jnp.asarray(toks),
                jnp.asarray(start), jnp.asarray(valid),
                jnp.asarray(last_idx))
        self._pkv = pkv
        with self._state_lock:
            self.stats["prefill_windows"] += 1
        return {"first": first, "rows": pref, "k": ks, "token": pf.token,
                "gen": {b: self._slot_gen[b] for b in pref},
                "t_disp": time.perf_counter()}

    def _finish_window(self, pend: Dict[str, Any]) -> None:
        """Complete a dispatched prefill window: advance per-row prompt
        positions and flip rows whose prompt just finished into decode
        (their first-token logits seed the stream). Async mode runs this
        one cycle AFTER the dispatch — the window launch precedes the next
        chunk on the pool's dependency chain, so by then its outputs are
        ready and the ``np.asarray`` below does not stall the loop — and
        scatters the transitions onto the device carry."""
        with span("serve.window_sync", self._tr):
            first = np.asarray(pend["first"])
        now = time.perf_counter()
        t_rows, t_len, t_last, t_rem = [], [], [], []
        done = []
        for b in pend["rows"]:
            if self._slot_gen[b] != pend["gen"][b] \
                    or self._slot_phase[b] != "prefill":
                continue                    # preempted since the dispatch
            prompt = self._slot_prompt[b]
            self._pref_pos[b] += pend["k"][b]
            self._lengths[b] = self._pref_pos[b]
            done.append(b)
            if self._tr is not None:
                self._tr.add("prefill_window", f"slot{b}",
                             pend["t_disp"], now,
                             {"req": self._slot_req[b].id,
                              "pos": int(self._pref_pos[b])})
            if self._pref_pos[b] >= len(prompt):
                req = self._slot_req[b]
                self._slot_phase[b] = "decode"
                self._last[b] = first[b]
                self._rem[b] = req.max_new - 1
                self._slot_out[b].append(int(first[b]))
                req.state = "decoding"
                self._note_first_token(req, now)
                if self._tr is not None:
                    self._phase_end(b, now, req)     # close "prefill"
                    self._phase_begin(b, "decode", now)
                self._wp_valid[b] = False
                self._register_prefix(b)
                t_rows.append(b)
                t_len.append(int(self._lengths[b]))
                t_last.append(int(first[b]))
                t_rem.append(req.max_new - 1)
        if self.async_decode and t_rows:
            self._scatter_carry(t_rows, t_len, t_last, t_rem,
                                pad_to=len(self._slot_req))
        self._log("prefill_chunk", pend["token"],
                  [(b, int(self._pref_pos[b])) for b in done])

    def _victim_score(self, v: int):
        """Cost-model preemption order (ascending = preempt FIRST). A
        victim is scored ``(tier, work lost net of blocks reclaimed,
        prior preemptions, age)``: best-effort tiers are always victimized
        before SLO tiers (tier-0 residents survive mixed-tier overload),
        then the row losing the least generated work per block reclaimed
        goes first, prior preemptions and youngest id as deterministic
        tiebreaks. Replaces the pure youngest-first rule, which happily
        evicted a tier-0 resident to feed a best-effort grow.

        Work-lost MUST outrank prior-preemption count: two same-tier
        residents contending for the same blocks alternate preemptions,
        so their counts leapfrog (c vs c+1) and a count-first order makes
        the established row score itself cheapest every time it grows —
        both rows self-evict forever (admit/replay livelock, zero
        retirements). Work-lost-first protects whichever row is furthest
        along, which is exactly the monotonic-progress guarantee the old
        youngest-first rule provided within a tier."""
        req = self._slot_req[v]
        out = self._slot_out[v]
        produced = len(out) if out is not None else 0
        blocks = self._slot_blocks[v] if self.paged else None
        held = len(blocks) if blocks is not None else 0
        return (-req.priority, produced - held, req.preempted_count,
                -req.id)

    def _grow_or_preempt(self, pf) -> None:
        """Phase 2 of two-phase admission: grant each decoding row the
        blocks the NEXT decode chunk will write into, oldest row first
        (lazy growth — a row crosses into a new block every ``block_size``
        tokens). Pool exhaustion preempts the best COST-MODEL victim
        (:meth:`_victim_score`: best-effort tier first, then least work
        lost per block reclaimed) back onto the wait queue instead of
        deadlocking: its blocks free immediately, the surviving rows keep
        decoding, and the preempted request re-runs from scratch later
        (greedy decode is deterministic, so its tokens are unchanged). A
        row never preempts a victim of a STRICTLY better (lower) tier —
        it stalls instead, so tier-0 residents are never evicted by
        best-effort growth.

        Async refinements: a growth failure while blocks sit behind the
        deferred-free fence STALLS the row (``rem`` masked to 0 on device,
        the balance parked in ``_stall_rem``) instead of preempting —
        preempting on in-transit memory could cascade into the oldest row
        evicting itself and replaying forever. Stalled rows retry here
        every cycle and resume the moment growth succeeds; their unmet
        block demand is RESERVED in the pool (oldest-stalled-first, since
        this pass runs in age order) so concurrent admissions cannot
        snatch the blocks the fence releases."""
        bs = self._pool.block_size
        n = self.decode_chunk
        fi = self._fi
        if fi is not None:
            if fi.fire("evict") and self._prefix is not None:
                self._prefix.evict(1)      # forced parked-prefix eviction
            if fi.fire("preempt"):
                live = [v for v in range(len(self._slot_req))
                        if self._slot_req[v] is not None]
                if live:
                    self._preempt(min(live, key=self._victim_score), pf)
        grow_rows: List[int] = []
        grow_cols: List[int] = []
        grow_ids: List[int] = []
        stall_rows: List[int] = []
        stall_vals: List[int] = []
        order = sorted((b for b in range(len(self._slot_req))
                        if self._slot_phase[b] == "decode"
                        and (self._rem[b] > 0 or self._stall_rem[b] > 0)),
                       key=lambda b: self._slot_req[b].id)
        # cost-model victim order, computed ONCE per cycle; slots preempted
        # along the way are skipped by the slot_req check
        victims = sorted((v for v in range(len(self._slot_req))
                          if self._slot_req[v] is not None),
                         key=self._victim_score)
        vi = 0
        for b in order:
            if self._slot_req[b] is None:
                continue                    # preempted as a victim already
            rem_b = int(self._rem[b]) + int(self._stall_rem[b])
            k = int(min(n, rem_b))
            need = (int(self._lengths[b]) + k - 1) // bs + 1
            cur = len(self._slot_blocks[b])
            covered = need <= cur
            while need > cur:
                if fi is not None and fi.fire("grow_fail"):
                    ids = None             # injected growth failure
                else:
                    # stalled/starved rows drain the reservation floor here
                    # (use_reserved): the pass runs oldest-first, so the
                    # oldest stalled row gets first claim on fence releases
                    ids = self._pool.grow_table(self._slot_blocks[b],
                                                need - cur,
                                                use_reserved=True)
                if ids is not None:
                    self._tables[b, cur:need] = ids
                    grow_rows.extend([b] * len(ids))
                    grow_cols.extend(range(cur, need))
                    grow_ids.extend(ids)
                    with self._state_lock:
                        self.stats["grown_blocks"] += len(ids)
                    if self._mh is not None:
                        self._mh["grown_blocks"].inc(len(ids))
                    covered = True
                    break
                if self._prefix is not None \
                        and self._prefix.evict(need - cur) > 0:
                    continue    # cold parked prefix blocks released: retry
                    # growth before stalling or preempting ANY resident row
                if self.async_decode and self._pool.num_deferred > 0:
                    break       # blocks in transit behind the fence: stall
                while vi < len(victims) \
                        and self._slot_req[victims[vi]] is None:
                    vi += 1
                if vi == len(victims):
                    break                   # nothing left to preempt
                victim = victims[vi]
                if self._slot_req[victim].priority \
                        < self._slot_req[b].priority:
                    # every remaining victim is of a strictly better tier
                    # than the grower (victims are ordered best-effort
                    # first): stall b rather than evict an SLO resident
                    break
                vi += 1
                self._preempt(victim, pf)
                if victim == b:
                    break                   # b itself was the best victim
            if self._slot_req[b] is None:
                continue                    # b preempted itself
            if covered:
                if self._stall_rem[b]:      # fence released: resume the row
                    self._rem[b] += self._stall_rem[b]
                    self._stall_rem[b] = 0
                    stall_rows.append(b)
                    stall_vals.append(int(self._rem[b]))
                    if self._tr is not None:
                        _t = time.perf_counter()
                        self._phase_end(b, _t, self._slot_req[b])  # stalled
                        self._phase_begin(b, "decode", _t)
                    self._log("resume", pf.token, b)
            elif self._rem[b] > 0:
                # newly stalled: mask the row out of the next dispatch
                self._stall_rem[b] = int(self._rem[b])
                self._rem[b] = 0
                stall_rows.append(b)
                stall_vals.append(0)
                with self._state_lock:
                    self.stats["stalls"] += 1
                if self._mh is not None:
                    self._mh["stalled"].inc()
                if self._tr is not None:
                    _t = time.perf_counter()
                    self._phase_end(b, _t, self._slot_req[b])  # close decode
                    self._phase_begin(b, "stalled", _t)
                self._log("stall", pf.token, b)
        # stalled-row reservation floor: the total block demand the pass
        # could not meet stays invisible to the admit stage until the
        # stalled rows (served oldest-first above) have been fed — the
        # structural fix for the admit-vs-stalled-row race
        unmet = 0
        for b in range(len(self._slot_req)):
            if self._stall_rem[b] > 0 and self._slot_req[b] is not None:
                k = int(min(n, self._stall_rem[b]))
                need = (int(self._lengths[b]) + k - 1) // bs + 1
                unmet += max(0, need - len(self._slot_blocks[b]))
        self._pool.set_reserved(unmet)
        if stall_rows and self.async_decode:
            # fixed-shape rem-only carry scatter (lengths/last unchanged —
            # `last` is device-only in async mode; pad with repeats)
            B = len(self._slot_req)
            rows = stall_rows + [stall_rows[-1]] * (B - len(stall_rows))
            vals = stall_vals + [stall_vals[-1]] * (B - len(stall_vals))
            ln, la, rm = self._carry
            self._carry = (ln, la, self._set_rem(
                rm, jnp.asarray(rows, jnp.int32),
                jnp.asarray(vals, jnp.int32)))
        if grow_rows:
            # device-side per-row table extension: the resident table array
            # is updated in place, not re-uploaded. Padded with repeats
            # (idempotent duplicate writes) to the worst-case burst size so
            # the scatter compiles exactly ONE shape per engine.
            self._log("grow", pf.token, list(zip(grow_rows, grow_ids)))
            m = self._grow_burst_max
            while len(grow_rows) < m:
                grow_rows.append(grow_rows[-1])
                grow_cols.append(grow_cols[-1])
                grow_ids.append(grow_ids[-1])
            self._tables_dev = self._extend_tables(
                self._tables_dev, jnp.asarray(grow_rows, jnp.int32),
                jnp.asarray(grow_cols, jnp.int32),
                jnp.asarray(grow_ids, jnp.int32))

    def _cow_guard(self, pf) -> None:
        """Copy-on-write safety net, run BEFORE the window-prefill and
        decode-chunk dispatches each cycle: any row about to WRITE into a
        block that is still shared (refcount > 1) forks it first — device
        block copy, table repoint (host mirror + device scatter), one
        reference dropped on the original. Structurally this never fires
        on the engine's own flows (admission forks partial tail blocks
        eagerly at the merge, and FULL shared prefix blocks are never
        written again by construction — decode appends land strictly past
        the prompt), but ``append_kv`` into a shared block corrupting a
        co-holder would be silent and unbounded, so the invariant is
        enforced here unconditionally (tests trigger it via an artificial
        ``incref``)."""
        if self._prefix is None:
            return
        bs = self._pool.block_size
        srcs, dsts, rows, cols = [], [], [], []
        for b in range(len(self._slot_req)):
            if self._slot_req[b] is None or self._slot_blocks[b] is None:
                continue
            if self._slot_phase[b] == "decode":
                lo = int(self._lengths[b])
                k = int(min(self.decode_chunk,
                            int(self._rem[b]) + int(self._stall_rem[b])))
            elif self._slot_phase[b] == "prefill":
                lo = int(self._pref_pos[b])
                k = int(min(self.prefill_chunk,
                            len(self._slot_prompt[b]) - lo))
            else:
                continue
            if k <= 0:
                continue
            blocks = self._slot_blocks[b]
            hi = min((lo + k - 1) // bs + 1, len(blocks))
            for col in range(lo // bs, hi):
                old = blocks[col]
                if self._pool.refcount(old) <= 1:
                    continue
                ids = self._pool.alloc(1)
                if ids is None:
                    self._prefix.evict(1)
                    ids = self._pool.alloc(1)
                if ids is None:
                    # cannot fork and must not write the shared block:
                    # requeue the row, it replays later (deterministic)
                    self._preempt(b, pf)
                    break
                new = ids[0]
                blocks[col] = new
                self._tables[b, col] = new
                srcs.append(old)
                dsts.append(new)
                rows.append(b)
                cols.append(col)
                # drop OUR reference on the original (co-holders keep it
                # alive; refcount stays >= 1 so nothing is released here)
                if self.async_decode:
                    self._pool.free_deferred([old])
                else:
                    self._pool.free([old])
                with self._state_lock:
                    self.stats["cow_forks"] += 1
                if self._tr is not None:
                    self._tr.instant("cow_fork", f"slot{b}",
                                     time.perf_counter(),
                                     {"req": self._slot_req[b].id,
                                      "src": int(old), "dst": int(new)})
        # a row preempted mid-pass (fork allocation failure) zeroed its
        # table and freed its blocks — drop its queued forks
        live = [j for j in range(len(rows))
                if self._slot_req[rows[j]] is not None]
        if len(live) < len(rows):
            srcs = [srcs[j] for j in live]
            dsts = [dsts[j] for j in live]
            rows = [rows[j] for j in live]
            cols = [cols[j] for j in live]
        if srcs:
            self._copy_blocks_padded(srcs, dsts)
            # device table repoint, padded with repeats (idempotent) to a
            # power of two like the copy
            m = 1 << max(0, len(rows) - 1).bit_length()
            ids2 = list(dsts)
            while len(rows) < m:
                rows.append(rows[-1])
                cols.append(cols[-1])
                ids2.append(ids2[-1])
            self._tables_dev = self._extend_tables(
                self._tables_dev, jnp.asarray(rows, jnp.int32),
                jnp.asarray(cols, jnp.int32), jnp.asarray(ids2, jnp.int32))

    def _clear_row_dev(self, slot: int) -> None:
        """Zero one vacated seat's device state: its block-table row
        (paged) and its carry row (async). Both scatters are PADDED to
        the admission cap with duplicate rows (idempotent writes) so
        they reuse the merge's compiled shapes — a 1-row scatter here
        would JIT-compile on the engine's FIRST preemption/eviction,
        a ~100ms+ stall landing exactly in the overloaded decode cycle
        the preemption was meant to relieve (it showed up as a 10x+
        tier-0 TTFT outlier in ``benchmarks/serve_slo.py``)."""
        A = self._scheduler.max_admit
        if self.paged:
            self._tables_dev = self._set_rows(
                self._tables_dev, jnp.asarray([slot] * A, jnp.int32),
                jnp.zeros((A, self._tables.shape[1]), jnp.int32))
        if self.async_decode:
            self._scatter_carry([slot], [0], [0], [0], pad_to=A)

    def _preempt(self, slot: int, pf) -> None:
        req = self._slot_req[slot]
        if not self.paged and not self.async_decode \
                and self._slot_phase[slot] == "decode":
            # SSM/hybrid sync mode: recurrent state is O(1)/seq, so a
            # CHECKPOINT preemption is cheap — capture the slot's exact
            # state + progress and re-seat it at the next admission with
            # no prefill replay. Async falls through to plain replay (the
            # in-flight chunk already advanced the donated state past the
            # host mirrors, so a capture here would be stale).
            req._ssm_ckpt = (self._save_slot_state(slot),
                             int(self._lengths[slot]),
                             int(self._last[slot]), int(self._rem[slot]),
                             list(self._slot_out[slot] or []))
        with self._state_lock:
            self._slot_req[slot] = None
            self._slot_out[slot] = None
            self._slot_phase[slot] = None
            if self.paged:
                if self.async_decode:
                    # deferred-free FENCE: the chunk in flight at
                    # preemption time (and any prefill window launched
                    # this cycle) may still write these blocks — they
                    # return to the pool only after the engine has synced
                    # past that device work
                    self._pool.free_deferred(self._slot_blocks[slot])
                else:
                    self._pool.free(self._slot_blocks[slot])
                self._slot_blocks[slot] = None
            self._free_slots.append(slot)
            self._inflight.discard(req)
            self.stats["preempted"] += 1
        self._slot_gen[slot] += 1      # in-flight tokens become surplus
        req.preempted_count += 1
        self._lengths[slot] = 0
        self._last[slot] = 0
        self._rem[slot] = 0
        if self.paged:
            self._slot_prompt[slot] = None
            self._wp_valid[slot] = False
            self._tables[slot] = 0
            self._stall_rem[slot] = 0
            self._pref_pos[slot] = 0
        self._clear_row_dev(slot)
        if self._mh is not None:
            self._mh["preempted"].inc()
            self._note_resident()
        if self._tr is not None:
            _t = time.perf_counter()
            self._phase_end(slot, _t, req)
            self._tr.instant("preempted", f"slot{slot}", _t, {"req": req.id})
        self._scheduler.requeue_front([req])
        self._log("preempt", pf.token, req.id)

    def _evict_row(self, slot: int, pf, err: BaseException,
                   kind: str) -> None:
        """Cancel/expire a SEATED row mid-flight: release its blocks/slot
        through the same paths preemption uses (deferred-free fence in
        async mode, seat-generation bump so in-flight chunk tokens are
        discarded) but fail the request typed instead of re-queueing it.
        ``kind`` is the stats/counter key (``"cancelled"``/``"expired"``).
        Works for both the paged and the SSM slot-state pools."""
        req = self._slot_req[slot]
        with self._state_lock:
            self._slot_req[slot] = None
            self._slot_out[slot] = None
            self._slot_phase[slot] = None
            if self.paged:
                if self.async_decode:
                    self._pool.free_deferred(self._slot_blocks[slot])
                else:
                    self._pool.free(self._slot_blocks[slot])
                self._slot_blocks[slot] = None
            self._free_slots.append(slot)
            self._inflight.discard(req)
            self.stats[kind] += 1
        self._slot_gen[slot] += 1      # in-flight tokens become surplus
        self._lengths[slot] = 0
        self._last[slot] = 0
        self._rem[slot] = 0
        if self.paged:
            self._slot_prompt[slot] = None
            self._wp_valid[slot] = False
            self._tables[slot] = 0
            self._stall_rem[slot] = 0
            self._pref_pos[slot] = 0
        self._clear_row_dev(slot)
        req.set_error(err)
        if self._journal is not None:
            self._journal.cancel(req, kind)
        if self._mh is not None:
            self._mh[kind].inc()
            self._note_resident()
        if self._tr is not None:
            _t = time.perf_counter()
            self._phase_end(slot, _t, req)
            self._tr.instant(kind, f"slot{slot}", _t, {"req": req.id})
        self._log(kind, pf.token, req.id)

    def _sweep_seated(self, pf) -> None:
        """Per-cycle SLO sweep, run in the SERIAL decode stage BEFORE the
        chunk dispatch (so the eviction scatters are sequenced ahead of
        it): cancel-requested rows and rows whose deadline elapsed
        mid-prefill/mid-decode are evicted — blocks and slot reclaimed
        through the normal (fence-aware) path, future failed typed. Also
        sweeps the WAITING queues so queued deadlines fire promptly even
        while admission is parked, and runs the admission-BOOST pass: a
        waiting head of a strictly better tier than the worst seated row
        must not wait out that row's whole decode when the batch is full,
        so the cost-model victim is preempted now and the next admit
        cycle seats the head (the victim replays later, bit-identically
        — greedy decode is deterministic)."""
        now = time.perf_counter()
        for b in range(len(self._slot_req)):
            req = self._slot_req[b]
            if req is None:
                continue
            if req._cancel_requested:
                self._evict_row(b, pf, RequestCancelled(
                    f"request {req.id} cancelled while {req.state}"),
                    "cancelled")
            elif req.expired(now):
                self._evict_row(b, pf, DeadlineExceeded(
                    f"request {req.id} deadline ({req.deadline_s:.3f}s) "
                    f"expired while {req.state} "
                    f"({now - (req.submitted_at or now):.3f}s after "
                    f"submit)"), "expired")
        self._scheduler.expire_waiting(now)
        if self._draining and self._drain_deadline_at is not None \
                and now >= self._drain_deadline_at:
            # drain deadline: checkpoint-preempt every resident (SSM sync
            # rows capture exact state; paged/async rows will replay) so
            # drain() can settle and the snapshot captures them as
            # waiting-queue descriptors. Runs here — the SERIAL decode
            # stage is the single writer of slot state — never on the
            # drain() caller thread.
            n = 0
            for b in range(len(self._slot_req)):
                if self._slot_req[b] is None:
                    continue
                self._preempt(b, pf)
                n += 1
            if n:
                with self._state_lock:
                    self.stats["drain_preempted"] += n
            return
        head = self._scheduler.peek_head()
        if head is None:
            return
        with self._state_lock:
            full = len(self._free_slots) <= self._slots_reserved
        if not full:
            return
        live = [v for v in range(len(self._slot_req))
                if self._slot_req[v] is not None]
        if not live:
            return
        victim = min(live, key=self._victim_score)
        if self._slot_req[victim].priority > head.priority:
            # one victim per cycle: enough to keep the SLO tier's TTFT
            # bounded by a cycle, without churning the whole batch
            self._preempt(victim, pf)

    def _isolate_failure(self, pf, exc: BaseException):
        """Per-row failure isolation: a raising decode/merge/sync step
        fails ONLY the rows it could have corrupted — every SEATED row and
        every admitted-but-unmerged group — with a typed
        :class:`RowFailed` (``__cause__`` carries the original exception),
        then rebuilds the device-resident state from scratch and keeps the
        engine serving: the WAITING queues survive untouched and re-run
        bit-identically (greedy decode is deterministic).

        The full rebuild (fresh block pool + zeroed KV pool) is not
        pessimism: the failed chunk call DONATED ``self._pkv``, so the old
        pool buffer is invalid whether or not the failure touched it. The
        reset epoch is bumped under the state lock — in-flight retire
        payloads and admitted groups from the old epoch are dropped at
        their epoch checks instead of freeing dead block ids into the
        fresh pool."""
        err = RowFailed(
            f"model step failed ({exc!r}); this row's seat was torn down "
            f"and the engine kept serving")
        err.__cause__ = exc
        B = len(self._slot_gen)
        now = time.perf_counter()
        # fresh device state FIRST, outside the lock (big allocations);
        # swapped in atomically below
        if self.paged:
            kv_blocks, block_size = self._kv_geom
            new_pool = BlockPool(kv_blocks, block_size)
            new_pkv = init_kv_pool(self.cfg, kv_blocks, block_size,
                                   tp=self._tp if self._pool_sharded else 1)
        else:
            new_state = {k: v
                         for k, v in lm.init_cache(self.cfg, B,
                                                   self._max_seq).items()
                         if k != "pos"}
        with self._state_lock:
            self._reset_epoch += 1
            seated = [(b, r) for b, r in enumerate(self._slot_req)
                      if r is not None]
            pre = [r for _, reqs in self._premerge.values() for r in reqs]
            self._premerge.clear()
            victims = {r.id: r for _, r in seated}
            victims.update((r.id, r) for r in pre)
            for r in victims.values():
                self._inflight.discard(r)
            self._slot_req = [None] * B
            self._slot_out = [None] * B
            self._slot_phase = [None] * B
            self._free_slots = list(range(B - 1, -1, -1))
            self._slots_reserved = 0
            if self.paged:
                self._pool = new_pool
                self._slot_blocks = [None] * B
            self.stats["row_failures"] += len(victims)
        # host mirrors + device arrays: decode-stage-owned, safe unlocked
        self._slot_gen += 1            # all in-flight tokens are surplus
        self._lengths[:] = 0
        self._last[:] = 0
        self._rem[:] = 0
        self._pending = None
        self._window_pending = None
        metrics = self.obs.metrics if self.obs is not None else None
        if self.paged:
            self._pkv = self._place_pool(new_pkv)
            self._stall_rem[:] = 0
            self._pref_pos[:] = 0
            self._wp_valid[:] = False
            self._tables[:] = 0
            self._tables_dev = self._dev(
                np.zeros(self._tables.shape, np.int32))
            self._slot_prompt = [None] * B
            self._pool.set_metrics(metrics)
            if self.prefix_cache:
                self._prefix = PrefixCache(self._pool)
                self._prefix.set_metrics(metrics)
        else:
            self._sstate = new_state if self._repl_ns is None \
                else jax.device_put(new_state, self._repl_ns)
        if self.async_decode:
            self._carry = (self._dev(np.zeros((B,), np.int32)),
                           self._dev(np.zeros((B,), np.int32)),
                           self._dev(np.zeros((B,), np.int32)))
        for b, r in seated:
            if self._tr is not None:
                self._phase_end(b, now, r)
        for r in victims.values():
            r.set_error(err)
        if self._mh is not None and victims:
            self._mh["row_failed"].inc(len(victims))
            self._note_resident()
        if self._tr is not None:
            self._tr.instant("row_failure_reset", TRACK_ENGINE, now,
                             {"failed": sorted(victims),
                              "epoch": self._reset_epoch,
                              "cause": repr(exc)})
        self._log("row_failure", pf.token,
                  {"failed": sorted(victims), "cause": repr(exc)})
        return ("cycle", (self._reset_epoch, []))

    def _st_decode(self, pf, msg):
        self._wd_beat = time.perf_counter()
        with span("serve.cycle", self._tr) as cyc:
            try:
                if self.async_decode:
                    out = self._st_decode_async(pf, msg, cyc)
                else:
                    out = self._st_decode_sync(pf, msg, cyc)
            except Exception as exc:       # per-row failure isolation
                out = self._isolate_failure(pf, exc)
        if self._mh is not None:
            self._mh["cycle"].record(cyc.seconds)
        self._wd_beat = time.perf_counter()
        return out

    def _end_cycle(self, cyc, emitted: int, dispatch_s: float,
                   sync_s: float) -> None:
        """Fold a decode cycle into the service rate and the per-cycle
        histograms; ``book_s`` is the cycle's host time outside the
        dispatch and the sync."""
        spent = time.perf_counter() - cyc.t0
        self._note_rate(emitted, spent)
        cyc.annotate(emitted=emitted)
        if self._mh is not None:
            mh = self._mh
            mh["dispatch"].record(dispatch_s)
            mh["sync"].record(sync_s)
            mh["book"].record(spent - dispatch_s - sync_s)
            mh["tokens_out"].inc(emitted)

    def _st_decode_sync(self, pf, msg, cyc):
        tr = self._tr
        kind, payload = msg
        if kind == "admit":
            with span("serve.merge", tr):
                if self.paged:
                    self._merge_group(pf, payload)
                else:
                    self._merge_group_slots(pf, payload)
        self._sweep_seated(pf)
        if self.paged:
            with span("serve.growth", tr):
                self._cow_guard(pf)
            self._window_prefill_step(pf)
            with span("serve.growth", tr):
                self._grow_or_preempt(pf)
        rem_before = self._rem.copy()
        if not (rem_before > 0).any():
            self._log("decode", pf.token, 0)
            return ("cycle", (self._reset_epoch, self._collect_finished()))
        n = self.decode_chunk
        with span("serve.dispatch", tr) as disp:  # carry upload + launch
            if self.paged:
                pkv, tok, ln, rm, toks = self._decode_paged(
                    self.params, self._pkv, self._tables_dev,
                    jnp.asarray(self._lengths), jnp.asarray(self._last),
                    jnp.asarray(self._rem), n=n)
                self._pkv = pkv
            else:
                st, tok, ln, rm, toks = self._decode_slots(
                    self.params, self._sstate, jnp.asarray(self._last),
                    jnp.asarray(self._lengths), jnp.asarray(self._rem), n=n)
                self._sstate = st
        with span("serve.sync", tr) as sync:
            if self._fi is not None:       # chunk-sync fault sites
                if self._fi.fire("chunk_latency"):
                    time.sleep(self._fi.latency_s("chunk_latency"))
                if self._fi.fire("chunk_sync_exc"):
                    raise FaultInjected("chunk_sync_exc")
                if self._fi.fire("crash_at"):
                    os._exit(137)          # hard mid-stream death, no cleanup
            toks = np.asarray(toks)        # (B, n): the chunk's device sync
        with span("serve.bookkeeping", tr):
            # np.array (not asarray): device views are read-only and these
            # mirrors are mutated by the next cycle's merge
            self._last = np.array(tok)
            self._lengths = np.array(ln)
            self._rem = np.array(rm)
            emitted = 0
            for b in np.nonzero(rem_before > 0)[0]:
                k = int(min(n, rem_before[b]))
                self._slot_out[b].extend(toks[b, :k].tolist())
                emitted += k
            with self._state_lock:
                self.stats["decode_cycles"] += 1
                self.stats["tokens_out"] += emitted
            retire = self._collect_finished()
        self._end_cycle(cyc, emitted, disp.seconds, sync.seconds)
        self._log("decode", pf.token, emitted)
        return ("cycle", (self._reset_epoch, retire))

    def _st_decode_async(self, pf, msg, cyc):
        """Async decode lookahead (pipeline depth 2): dispatch chunk N+1
        FIRST — JAX async dispatch queues it behind the in-flight chunk N,
        so the device-side dependency chain never drains — then sync chunk
        N's tokens and do all host bookkeeping (emit tokens, retire
        finished rows, advance the deferred-free fence) while N+1 runs.
        Admission merges, streamed prefill windows and table growth are
        sequenced BEFORE the dispatch; retirement takes effect one chunk
        late (already masked on device by ``rem == 0``); a preempted row's
        in-flight tokens are discarded via the seat-generation guard."""
        tr = self._tr
        kind, payload = msg
        pend = self._pending
        # ---- pre-dispatch: everything chunk N+1 must observe ----
        wpend, self._window_pending = self._window_pending, None
        if wpend is not None:
            self._finish_window(wpend)
        if kind == "admit":
            with span("serve.merge", tr):
                if self.paged:
                    self._merge_group(pf, payload)
                else:
                    self._merge_group_slots(pf, payload)
        self._sweep_seated(pf)
        if self.paged:
            with span("serve.growth", tr):
                self._cow_guard(pf)
            self._window_pending = self._dispatch_window_prefill(pf)
            with span("serve.growth", tr):
                self._grow_or_preempt(pf)
        # ---- dispatch chunk N+1 (the device never waits on the host
        # bookkeeping below) ----
        n = self.decode_chunk
        new_pend = None
        dispatch_s = sync_s = 0.0
        if (self._rem > 0).any():
            rem_before = self._rem.copy()
            with span("serve.dispatch", tr) as disp:
                if self.paged:
                    pkv, tok, ln, rm, toks = self._decode_paged(
                        self.params, self._pkv, self._tables_dev,
                        *self._carry, n=n)
                    self._pkv = pkv
                else:
                    lengths, last, rem = self._carry
                    st, tok, ln, rm, toks = self._decode_slots(
                        self.params, self._sstate, last, lengths, rem, n=n)
                    self._sstate = st
            dispatch_s = disp.seconds
            self._carry = (ln, tok, rm)
            # advance the host lengths/rem mirrors deterministically (the
            # chunk's length/rem arithmetic is token-independent); the
            # host `last` mirror stays stale — it is never read in async
            # mode, the device carry is authoritative
            adv = np.minimum(n, rem_before)
            self._lengths += adv
            self._rem -= adv
            new_pend = {"toks": toks, "rem_before": rem_before,
                        "gen": self._slot_gen.copy(), "token": pf.token}
            with self._state_lock:
                self.stats["decode_cycles"] += 1
            self._log("dispatch", pf.token, int((rem_before > 0).sum()))
        # ---- sync chunk N + host bookkeeping (overlaps N+1 on device) ----
        emitted = 0
        if pend is not None:
            with span("serve.sync", tr) as sync:
                if self._fi is not None:   # chunk-sync fault sites
                    if self._fi.fire("chunk_latency"):
                        time.sleep(self._fi.latency_s("chunk_latency"))
                    if self._fi.fire("chunk_sync_exc"):
                        raise FaultInjected("chunk_sync_exc")
                    if self._fi.fire("crash_at"):
                        os._exit(137)  # hard mid-stream death, no cleanup
                toks = np.asarray(pend["toks"])
            sync_s = sync.seconds
        with span("serve.bookkeeping", tr):
            if pend is not None:
                for b in np.nonzero(pend["rem_before"] > 0)[0]:
                    if self._slot_gen[b] != pend["gen"][b]:
                        continue    # seat changed since dispatch: surplus
                    k = int(min(n, pend["rem_before"][b]))
                    self._slot_out[b].extend(toks[b, :k].tolist())
                    emitted += k
                with self._state_lock:
                    self.stats["tokens_out"] += emitted
                self._log("sync", pf.token, (pend["token"], emitted))
            self._pending = new_pend
            retire = self._collect_finished()
            if self.paged and (pend is not None or (
                    new_pend is None and self._window_pending is None)):
                # fence advance: a chunk was synced (or nothing is in
                # flight at all) — blocks deferred two advances ago are
                # now provably past every device write that could touch
                # them
                self._pool.release_deferred()
        self._end_cycle(cyc, emitted, dispatch_s, sync_s)
        self._log("decode", pf.token, emitted)
        return ("cycle", (self._reset_epoch, retire))

    def _collect_finished(self) -> List[tuple]:
        """Rows that hit rem==0: detach them from the batch (their slot
        stays reserved until complete frees it) and zero their mirrors —
        still inside the SERIAL decode stage (single-writer); the
        gather-free read paths bound their page loop by max(lengths), so a
        retired slot must not keep advertising its old length.

        Async mode retires one chunk LATE by construction: a row that hit
        ``rem == 0`` during chunk N is collected only after N's sync —
        rows still finishing inside the freshly dispatched chunk (or
        stalled behind the deferred-free fence) are skipped, and the
        zeroing scatters land on the in-flight chunk's OUTPUT carry/
        tables (the retired rows are already inactive in that chunk), so
        the detach never races device work."""
        pend = self._pending
        retire = []
        zero_rows = []
        for b in range(len(self._rem)):
            if self._slot_req[b] is None or self._slot_phase[b] != "decode" \
                    or self._rem[b] != 0:
                continue
            if self.paged and self._stall_rem[b] > 0:
                continue        # stalled (fence or tier guard), not finished
            if pend is not None and pend["rem_before"][b] > 0:
                continue        # active in the in-flight chunk: next cycle
            req = self._slot_req[b]
            out = np.asarray(self._slot_out[b], np.int32)
            with self._state_lock:
                self._slot_req[b] = None
                self._slot_out[b] = None
                self._slot_phase[b] = None
            self._slot_gen[b] += 1
            self._lengths[b] = 0
            self._last[b] = 0
            if self.paged:
                self._tables[b] = 0
                self._pref_pos[b] = 0
                self._slot_prompt[b] = None
            zero_rows.append(b)
            retire.append((b, req, out))
            if self._tr is not None:
                _t = time.perf_counter()
                self._phase_end(b, _t, req)
                self._tr.instant("retired", f"slot{b}", _t,
                                 {"req": req.id, "tokens": len(out)})
        if zero_rows:
            # fixed-shape zeroing scatters (pad with repeats; idempotent)
            B = len(self._slot_req)
            z = [0] * len(zero_rows)
            if self.async_decode:
                self._scatter_carry(zero_rows, z, z, z, pad_to=B)
            if self.paged:
                rows = zero_rows + [zero_rows[-1]] * (B - len(zero_rows))
                self._tables_dev = self._set_rows(
                    self._tables_dev, jnp.asarray(rows, jnp.int32),
                    jnp.zeros((B, self._tables.shape[1]), jnp.int32))
        return retire

    def _st_complete(self, pf, msg):
        _, (epoch, retire) = msg
        now = time.perf_counter()
        for slot, req, out in retire:
            # a retiree's TOKENS are always valid (it finished before any
            # failure), so its future is fulfilled unconditionally; its
            # blocks/slot are reclaimed only if no failure-isolation reset
            # rebuilt the pool since the decode stage collected it (the
            # epoch check and the frees are atomic against the reset, which
            # swaps the pool under the same lock)
            self._scheduler.finish(req, out, now)
            if self._journal is not None:
                self._journal.finish(req, out)
            with self._state_lock:
                self._inflight.discard(req)
                self.stats["retired"] += 1
                if epoch == self._reset_epoch:
                    if self.paged:
                        self._pool.free(self._slot_blocks[slot])
                        self._slot_blocks[slot] = None
                    self._free_slots.append(slot)
        self._wd_beat = now
        with self._state_lock:
            self._cycle_tokens.discard(pf.token)
        if retire and self._mh is not None:
            self._mh["retired"].inc(len(retire))
            self._note_resident()
        self._log("complete", pf.token, len(retire))
        return None

    # --------------------------------------------------------------- pumping
    def _pump(self) -> None:
        ex = self._ensure_executor()
        pl = self._ensure_pipeline(ex)
        with self._pump_lock:
            if self._broken is not None or not pl.idle():
                return
            with self._state_lock:
                occupied = any(r is not None for r in self._slot_req)
            if self._scheduler.num_waiting == 0 and not occupied:
                return
            self._topo = pl.run(ex, self._on_topo_done)

    def _on_topo_done(self, topo) -> None:
        if topo.exceptions:
            err = topo.exceptions[0]
            self._broken = err
            self._fail_outstanding(err)
            return
        if self._scheduler.num_waiting:
            self._pump()   # a submit raced the stop-drain: re-arm

    def _fail_outstanding(self, err: BaseException) -> None:
        self._scheduler.fail_all_waiting(err)
        with self._state_lock:
            live = list(self._inflight)  # admitted: slotted or pre-merge
            self._inflight.clear()
        for r in live:
            r.set_error(err)

    # ----------------------------------------------------------- client API
    def _shed_budget_for(self, tier: int) -> Optional[float]:
        """Resolve the load-shed latency budget for a tier: a scalar
        budget applies to every tier, a dict only to its listed tiers
        (absent tiers are never shed)."""
        b = self._shed_budget
        if b is None:
            return None
        if isinstance(b, dict):
            v = b.get(tier)
            return float(v) if v is not None else None
        return float(b)

    def _note_rate(self, emitted: int, dt: float) -> None:
        """Fold one decode cycle into the observed service rate: emitted
        tokens over the cycle's WALL time (device chunk + host
        bookkeeping — the rate the backlog actually drains at). Cycles
        that emitted nothing (pure prefill/admission cycles) are skipped
        rather than averaged in as zero: they stall emission but their
        cost is already inside the neighbouring cycles' wall time."""
        if emitted <= 0 or dt <= 0.0:
            return
        r = emitted / dt
        a = self._rate_alpha
        self._decode_rate = r if self._decode_rate == 0.0 \
            else (1.0 - a) * self._decode_rate + a * r

    def _estimated_wait_s(self, priority: int) -> Optional[float]:
        """Admission-wait estimate for a NEW request at ``priority``.

        Primary model — SERVICE RATE: the engine's observed decode
        throughput (EWMA tokens/s over whole cycles, :meth:`_note_rate`)
        divides the work queued ahead of the request: every resident
        row's remaining decode steps (including fence-stalled balances)
        plus the ``max_new`` of everything waiting at tiers <= the
        request's. This tracks load directly — it rises the moment the
        backlog grows, rather than waiting for slow admissions to feed
        the queue-wait histogram.

        Fallback — the pre-existing p90-queue-wait heuristic (the p90 of
        ``serve.queue_wait_s`` scaled by the tier-visible backlog in
        admission waves), used only until the engine has emitted its
        first tokens. It still arms only after 8 recorded admissions, so
        a cold-start engine never sheds. Returns None when neither model
        has a signal."""
        rate = self._decode_rate
        if rate > 0.0:
            resident = 0
            # lock-free mirror reads (heuristic: same policy as the
            # watchdog's busy probe — at worst one cycle stale)
            for b in range(len(self._rem)):
                if self._slot_req[b] is None:
                    continue
                resident += int(self._rem[b])
                if self.paged:
                    resident += int(self._stall_rem[b])
            backlog = self._scheduler.waiting_tokens_upto(priority)
            return (resident + backlog) / rate
        if self._mh is None:
            return None
        h = self._mh["qwait"]
        if h.count < 8:
            return None
        base = h.percentile(90.0)
        backlog = self._scheduler.num_waiting_upto(priority)
        waves = 1.0 + backlog / float(self._scheduler.max_admit)
        return base * waves

    def _hopeless_why(self, r: ServeRequest) -> Optional[str]:
        """Preemption-aware deadline check at the admission head: a
        deadline request whose remaining budget cannot cover its
        estimated prefill + decode at the observed service rate is
        failed typed :class:`DeadlineExceeded` NOW, before it steals a
        slot (and possibly preempts a resident via the admission boost)
        only to expire mid-decode anyway. Conservative: with no rate
        signal yet (cold engine) nothing is ever hopeless."""
        if r.deadline_at is None:
            return None
        rate = self._decode_rate
        if rate <= 0.0:
            return None
        remaining = r.deadline_at - time.perf_counter()
        est = (r.prompt_len + r.max_new) / rate
        if est <= remaining:
            return None
        return (f"hopeless at admission: estimated prefill+decode "
                f"{est:.3f}s exceeds the remaining deadline budget "
                f"{remaining:.3f}s at the observed service rate "
                f"{rate:.1f} tok/s")

    def submit(self, prompt, max_new: int = 16, *,
               priority: int = 0,
               deadline_s: Optional[float] = None) -> ServeRequest:
        """Enqueue one generation request on the resident pipeline and
        return its future. Thread-safe; callable while earlier requests are
        mid-decode — that is the point. All architectures: paged attention
        KV for dense/MoE models, the fixed-slot recurrent-state pool for
        SSM/hybrid ones.

        ``priority`` is the scheduling tier (0 = highest; admission scans
        tiers in order, the preemption cost model victimizes the highest
        tier first). ``deadline_s`` is an optional latency bound from now:
        a request that has not completed within it fails typed
        :class:`DeadlineExceeded` whether it is queued or mid-decode, and
        its resources are reclaimed. When a shed budget is configured for
        the tier (``shed_budget_s``), an over-budget estimated queue wait
        raises :class:`Overloaded` HERE — synchronously, before the
        request ever queues — so callers can back off or retry elsewhere."""
        if self._broken is not None:
            raise RuntimeError("serve pipeline is broken") from self._broken
        if self._closing:
            raise EngineClosed("engine is closed")
        if self._draining:
            raise EngineClosed(
                "engine is draining: admission stopped; submit to another "
                "replica (residents run to the drain deadline)")
        req = ServeRequest(prompt, max_new, priority=priority,
                           deadline_s=deadline_s)
        total = req.prompt_len + req.max_new
        if total > self._max_seq:
            raise ValueError(
                f"prompt+max_new = {total} exceeds max_seq_len "
                f"{self._max_seq}")
        budget = self._shed_budget_for(req.priority)
        if budget is not None:
            est = self._estimated_wait_s(req.priority)
            limit = budget if deadline_s is None \
                else min(budget, deadline_s)
            if est is not None and est > limit:
                with self._state_lock:
                    self.stats["shed"] += 1
                if self._mh is not None:
                    self._mh["shed"].inc()
                depth = self._scheduler.num_waiting_upto(req.priority)
                raise Overloaded(
                    f"request shed at submit: estimated queue wait "
                    f"{est:.3f}s exceeds the tier-{req.priority} budget "
                    f"{limit:.3f}s (backlog {depth} at tiers <= "
                    f"{req.priority})",
                    tier=req.priority, est_wait_s=est, budget_s=limit,
                    queue_depth=depth)
        now = time.perf_counter()
        req.submitted_at = now
        if req.deadline_s is not None:
            req.deadline_at = now + req.deadline_s
        if self._journal is not None:
            self._journal.submit(req)
        self._wd_beat = now
        self._scheduler.enqueue(req)
        self._pump()
        return req

    def result(self, req: ServeRequest,
               timeout: Optional[float] = 300.0) -> np.ndarray:
        return req.result(timeout)

    def generate(self, prompts: List[Any], max_new: int) -> List[Any]:
        """Compatibility shim: submit every prompt, gather results in input
        order. Greedy tokens are bit-identical to the per-call engine this
        replaces (same compiled prefill math, same argmax chain — verified
        against the contiguous reference in tests)."""
        if not prompts:
            return []
        reqs = [self.submit(p, max_new) for p in prompts]
        return [self.result(r, timeout=600.0) for r in reqs]

    # -------------------------------------------- per-call baseline (bench)
    def _generate_grouped(self, prompts: List[Any], max_new: int
                          ) -> List[Any]:
        """PR 1's per-call pipeline: length groups flow admit -> prefill ->
        chunked contiguous decode -> complete through a throwaway
        DataPipeline. No longer a serving fallback (submit()/result() covers
        every arch through the resident pipeline); kept as the per-call
        BASELINE the serve benchmark compares against and as a bit-identity
        reference in tests."""
        groups: "OrderedDict[int, List[int]]" = OrderedDict()
        arrs = [np.asarray(p, np.int32) for p in prompts]
        for i, a in enumerate(arrs):
            groups.setdefault(len(a), []).append(i)
        work = deque(groups.values())
        results: List[Any] = [None] * len(prompts)

        def admit(pf):
            if not work:
                pf.stop()
                return None
            return work.popleft()

        def prefill(pf, idxs):
            toks = np.stack([arrs[i] for i in idxs])
            max_len = toks.shape[1] + max_new + 1
            logits, cache = self._prefill(self.params, jnp.asarray(toks),
                                          None, max_len=max_len)
            cur = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return idxs, cache, cur

        def decode(pf, state):
            idxs, cache, cur = state
            chunks = [cur[:, None]]
            remaining = max_new - 1
            while remaining > 0:
                n = min(self.decode_chunk, remaining)
                cache, chunk = self._decode_n(self.params, cache, cur, n)
                chunks.append(chunk)
                cur = chunk[:, -1]
                remaining -= n
            return idxs, chunks

        def complete(pf, state):
            idxs, chunks = state
            seqs = np.concatenate([np.asarray(c) for c in chunks], axis=1)
            for row, i in enumerate(idxs):  # rows scatter to disjoint slots
                results[i] = seqs[row]
            return None

        ex = self._ensure_executor()
        decode_domain = ACCEL if ex.has_domain(ACCEL) else HOST
        pl = DataPipeline(
            max(1, min(len(work), self.pipeline_lines)),
            DataPipe(PipeType.SERIAL, admit, name="admit"),
            DataPipe(PipeType.SERIAL, prefill, name="prefill"),
            DataPipe(PipeType.SERIAL, decode, name="decode",
                     domain=decode_domain),
            DataPipe(PipeType.PARALLEL, complete, name="complete"),
            name="serve-generate")
        pl.run(ex).wait()
        return results
