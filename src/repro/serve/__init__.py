"""Continuous-batching serve subsystem.

* :mod:`.engine`    — the resident admit→prefill→decode→complete pipeline
  (``submit()`` / ``result()``; ``generate()`` compatibility shim), serving
  EVERY architecture: attention models through the paged KV pool, SSM and
  hybrid models (mamba, zamba2) through a fixed-slot recurrent-state pool;
* :mod:`.scheduler` — TIERED request queues + admission control (no
  length buckets) budgeted on prompt-only footprints (minus any
  cached-prefix blocks when prefix caching is on): strict priority
  across tiers, earliest-deadline-first within a tier (deadline-less
  requests keep FIFO order behind any deadlines), optional guaranteed
  best-effort admission shares (``tier_targets``), queue-deadline expiry
  and lazy cancellation sweeps;
* :mod:`.errors`    — the typed failure vocabulary (``ServeError`` and
  subclasses: ``Overloaded``, ``DeadlineExceeded``, ``RequestCancelled``,
  ``RowFailed``, ``WatchdogTimeout``, ``EngineClosed``) that
  ``result()`` re-raises directly;
* :mod:`.faultinject` — the deterministic fault-injection harness
  (``REPRO_FAULT_INJECT`` / ``ServeEngine(fault_inject=...)``; seeded
  per-site schedules, see ``docs/robustness.md``);
* :mod:`.journal`   — the durability WAL: checksummed request-transition
  records, torn-tail truncating :func:`~repro.serve.journal.replay`
  (crashed requests re-submit and replay bit-identically under greedy
  decode);
* :mod:`.snapshot`  — the checksummed, versioned, pickle-free engine
  snapshot container (prefix trie + parked KV pages + waiting-queue
  descriptors; ANY integrity failure is typed ``SnapshotCorrupt`` and
  recovery cold-starts);
* :mod:`.kvcache`   — paged KV-cache pool (REFCOUNTED block allocator with
  mid-decode ``grow_table`` + jit-able fused K/V scatters through
  per-sequence block tables, including the chunked-prefill
  ``scatter_token_window``, the device-side ``extend_block_tables`` growth
  scatter and the copy-on-write ``copy_blocks`` fork; the ``gather_pages``
  reference read path);
* :mod:`.prefix`    — the prefix cache: a hash trie over block-aligned
  prompt chunks mapping cached prefixes to live pool blocks, with
  pin/park/reuse-scored-evict semantics (see ``docs/prefix_caching.md``).

Two-phase admission semantics
-----------------------------
Memory admission is split into two phases so pool capacity follows LIVE
token counts instead of worst-case reservations:

* **Phase 1 — admit on the prompt footprint.** A request joins the running
  batch as soon as free decode slots exist and the pool covers
  ``blocks_for(prompt_len)`` — not ``prompt + max_new``. Admission is
  strictly FIFO from one queue; because chunked prefill fixes the compiled
  window shape, mixed prompt lengths admit together in one group / one
  prefill launch.
* **Chunked prefill.** A prompt longer than ``prefill_chunk`` lands window
  by window: window 0 through the prefill stage, the rest streamed by the
  decode stage one window per pipeline cycle, each scattered straight into
  the paged pool through the row's block table — resident rows keep
  decoding in the overlapped cycles, so a long prompt never stalls the
  batch behind one monolithic launch.
* **Phase 2 — grow mid-decode.** Every ``block_size`` generated tokens a
  row crosses into a new block; the decode stage grants it lazily
  (``BlockPool.grow_table`` + an in-place device-side table-extension
  scatter). If the pool is exhausted, the best COST-MODEL victim is
  preempted — best-effort tier first, then least generated work lost
  per block reclaimed, prior preemptions and age as tiebreaks (tier-0
  residents survive mixed-tier overload; a grower never evicts a
  strictly better-tier victim, it stalls instead). The victim's blocks free
  immediately, its request re-queues at its tier's line position
  (greedy decode is deterministic, so the re-run emits identical
  tokens) — back-pressure degrades to queueing, never deadlock.

SLO-aware overload control
--------------------------
``submit(prompt, max_new, priority=..., deadline_s=...)`` places a
request on a scheduling TIER (0 = highest) with an optional latency
bound; ``ServeRequest.cancel()`` withdraws it from any state. Engine
knobs (see ``docs/robustness.md`` for the full policy): ``tier_targets``
guarantees backlogged best-effort tiers a minimum admission share;
``shed_budget_s`` (scalar or per-tier dict; ``REPRO_SHED_BUDGET_S``)
makes ``submit()`` raise typed ``Overloaded`` when the live estimated
queue wait exceeds the tier's budget — estimated from a service-rate
model (observed decode tokens/s vs resident remaining work plus the
waiting backlog at or above the request's tier), falling back to the
p90-queue-wait heuristic only before any rate sample exists;
``watchdog_s``
(``REPRO_WATCHDOG_S``) arms a stuck-engine monitor that fails all
outstanding futures typed ``WatchdogTimeout``; ``fault_inject``
(``REPRO_FAULT_INJECT``) enables the deterministic fault-injection
harness. Expiry/cancellation of SEATED rows reclaims blocks and seats
through the normal fence-aware eviction path; a raising prefill/decode
step fails only its blast radius typed ``RowFailed`` and the engine
rebuilds device state and keeps serving (per-row failure isolation);
``close()`` fails anything still outstanding typed ``EngineClosed``.
``benchmarks/serve_slo.py`` measures the resulting tier-0 tail-TTFT
protection under a best-effort flood.

SSM/hybrid architectures have no per-token KV to page; their O(1)-per-
sequence recurrent state (and zamba2's shared-block KV span) lives in a
fixed-slot state pool indexed by decode slot, so ``submit()``/``result()``
continuous batching covers them through the same resident pipeline
(:func:`repro.models.lm.decode_step_slots`); admission for them is
bounded by free slots alone.

Prefix caching (copy-on-write KV block sharing)
-----------------------------------------------
``ServeEngine(prefix_cache=True)`` (or ``REPRO_PREFIX_CACHE=1``) indexes
every admitted prompt's full ``block_size``-token chunks in a hash trie
and lets later admissions SHARE the pool blocks already holding that
prefix's KV:

* a cache-hit admission budgets only its uncached suffix blocks, seeds
  its block table with the shared blocks, and starts its prefill window
  walk at the first uncached token (``serve.prefill_tokens_saved``);
* a hit ending mid-block is consumed by a copy-on-write FORK (one device
  block copy + table repoint) before the row's own writes land, so
  co-holders keep reading the original bits — and a ``_cow_guard`` pass
  enforces fork-before-write on every dispatch, sync and async;
* retired requests' prefix blocks stay PARKED (held only by the index);
  under pool pressure the engine evicts cold parked blocks by reuse
  score (hits x recency, leaf-first) BEFORE preempting any resident row.

Off by default; the uncached path is bit-exact unchanged, and cached
greedy streams are bit-identical to uncached on the gather oracle
(``tests/test_prefix_cache.py``). Attention/paged serving only — SSM
recurrent state has no block-granular prefix identity (follow-up).

Async decode lookahead
----------------------
``ServeEngine(async_decode=True)`` (or ``REPRO_ASYNC_DECODE=1``) pipelines
the decode loop one chunk deep so host scheduling overlaps device compute:

* the decode carry ``(lengths, last, rem)`` is DEVICE-RESIDENT across
  cycles — chunk N+1 consumes chunk N's output carry directly, and
  admission merges / prefill-window completions / retirement / preemption
  mutate it via the same fixed-shape padded scatters the block-table array
  uses (:func:`repro.serve.kvcache.set_carry_rows`);
* each cycle the SERIAL decode stage runs **dispatch -> sync**: chunk N+1
  is dispatched first (queued behind N by JAX async dispatch), then chunk
  N's tokens are synced and all host bookkeeping runs while N+1 computes.

Consequences, handled explicitly: retirement takes effect ONE CHUNK LATE
(the finished row stays masked on device by ``rem == 0``; its surplus
in-flight tokens are discarded host-side by a seat-generation guard), and
a preempted row's blocks re-enter the pool only after the engine has
synced past the device work that could still write them (the
``BlockPool.free_deferred`` / ``release_deferred`` fence). Greedy tokens
are bit-identical to the synchronous engine, which remains the reference
path (default off). Both loops mark their phases as ``serve.dispatch`` /
``serve.sync`` / ``serve.bookkeeping`` spans (Observability below).

Tensor-parallel sharded serving
-------------------------------
``ServeEngine(cfg, params, ctx=make_ctx(small_mesh(data=1, model=N)))``
— or ``REPRO_MESH_MODEL=N`` — shards the serve data plane over the mesh
``model`` axis (see ``docs/sharded_serving.md``): the paged KV pool is
partitioned by KV HEAD (per-device footprint ~1/N), attention/MLP
weights are column-sharded on their output dim, and the compiled decode
chunk runs under ``shard_map`` with only activation-sized tiled
all-gathers — never a psum, so greedy decode stays BIT-IDENTICAL to the
single-device engine (sync and async, chunked prefill, growth/
preemption, prefix caching). Block tables, the decode carry and SSM slot
state stay replicated. An explicit mesh whose axis cannot divide the
model's head/feature counts is refused with typed
``MeshDivisibilityError``; the env knob clamps to the largest usable
divisor instead. ``tests/test_serve_mesh.py`` asserts both the parity
matrix and — via :mod:`repro.distributed.hlo_analysis` — that the
lowered decode HLO contains no all-reduce and no all-gather anywhere
near the pool-shard size (the no-accidental-gather invariant).

Paged read-path selection
-------------------------
The compiled decode chunk reads the KV pool through one of three
implementations, chosen by ``ServeEngine(paged_impl=...)`` (or the
``REPRO_PAGED_IMPL`` environment variable when left unset; see
:func:`repro.kernels.ops.default_paged_impl`):

* ``"pallas"`` — gather-free Pallas kernel
  (:mod:`repro.kernels.paged_attention`): pages are read in place through
  the scalar-prefetched block table, blocks past each row's length are
  skipped. Mosaic lowering on TPU; interpreter (correctness only)
  elsewhere. Default on TPU.
* ``"xla"``    — the same blockwise online-softmax algorithm as a
  traced-bound page loop: per-row cost follows batch occupancy, not pool
  capacity. Default off TPU.
* ``"gather"`` — the original materialize-then-mask path
  (``kvcache.gather_pages``): O(max_blocks) HBM traffic and FLOPs per row
  per layer per token regardless of true length. Kept as the reference
  oracle (``tests/test_paged_attention.py`` checks both gather-free paths
  against it).

Observability
-------------
``ServeEngine(obs=repro.obs.Observability())`` — or ``REPRO_OBS=1`` in the
environment — turns on the serve-layer observability stack
(:mod:`repro.obs`; see ``docs/observability.md`` for a quick-start):

* **Spans** (:func:`repro.obs.tracing.span`): the engine's phases
  (``serve.admission``, ``serve.prefill``, ``serve.merge``,
  ``serve.prefill_window``, ``serve.window_sync``, ``serve.growth``, and
  ``serve.cycle`` with its ``serve.dispatch``/``serve.sync``/
  ``serve.bookkeeping`` split) and the pipe bodies (``pipe.admit`` …
  ``pipe.complete``) always reach a ``jax.profiler`` trace, obs or not,
  on the device trace's clock. With obs attached they also land in the
  ring-buffer :class:`repro.obs.Tracer`: ``serve.*`` on the ``"engine"``
  track, ``pipe.*`` on ``lineN`` tracks (``Pipeline.stage_times``
  promoted to a timeline); and each decode SLOT is a track carrying its
  seated request's lifecycle — ``queued`` → ``admitted`` →
  ``prefill``/``prefill_window`` → ``decode`` → ``stalled`` — plus
  ``retired``/``preempted`` instants; a preempted request re-enters with a
  fresh queued/admitted chain, so the track replays every re-entry.
* **Metrics** (:class:`repro.obs.MetricsRegistry`): counters
  ``serve.tokens_out`` / ``serve.requests.{admitted,retired,preempted,
  stalled}`` / ``serve.{shed,expired,cancelled,watchdog_fires,
  row_failures}`` / ``pool.grown_blocks`` /
  ``prefix.{hits,misses,evicted}`` / ``serve.prefill_tokens_saved``;
  gauges ``serve.queue_depth`` / ``serve.resident_rows`` /
  ``pool.blocks_{free,used,deferred,shared,parked,reserved}``;
  histograms ``serve.ttft_s`` (plus lazy per-tier
  ``serve.ttft_s.tierN``) / ``serve.queue_wait_s`` /
  ``engine.{cycle,dispatch,chunk_sync,book}_s``; per-slot
  ``cow_fork`` trace instants mark copy-on-write block forks.
* **Export**: ``obs.export(path)`` writes Chrome trace-event JSON that
  loads directly in Perfetto (https://ui.perfetto.dev) or
  ``chrome://tracing``; ``repro.launch.serve --stats-interval N --trace
  out.json`` prints a one-line stats summary per interval and writes the
  artifact on exit. Requests themselves carry lifecycle timestamps
  (:attr:`ServeRequest.submitted_at` / ``admitted_at`` /
  ``first_token_at`` / ``finished_at`` and the derived ``ttft`` /
  ``queue_wait``).

A ``None`` obs handle (the default) leaves each span its profiler
annotation and two clock reads, and every other hot path a single
attribute check.

Durable serving
---------------
Off by default, composable on (``docs/robustness.md`` "Durability &
recovery"): attach a :class:`~repro.serve.journal.Journal` (or pass
``--state-dir`` to ``repro.launch.serve``) and every request transition
lands in a checksummed WAL; ``ServeEngine.recover(state_dir)`` replays
a crashed engine's incomplete requests bit-identically and warm-starts
the prefix cache from the last ``ServeEngine.snapshot`` (corruption is
typed ``SnapshotCorrupt`` → cold start, never wrong tokens);
``ServeEngine.drain(deadline_s=...)`` gates admission and
checkpoint-preempts residents past the deadline (sync SSM/hybrid rows
capture recurrent slot state and resume without re-prefill). The
launcher turns SIGTERM into drain → snapshot → close.
``benchmarks/journal_overhead_gate.py`` enforces the journaled-path
budget; the no-journal path is one ``is None`` check per transition.
"""
from .engine import JOURNAL_FILE, SNAPSHOT_FILE, ServeEngine
from .errors import (DeadlineExceeded, EngineClosed, Overloaded,
                     RequestCancelled, RowFailed, ServeError,
                     SnapshotCorrupt, WatchdogTimeout)
from .faultinject import FaultInjected, FaultInjector
from .journal import Journal, JournalReplay, replay
from .kvcache import BlockPool, init_kv_pool
from .scheduler import Scheduler, ServeRequest
from .snapshot import read_snapshot, write_snapshot

__all__ = ["ServeEngine", "ServeRequest", "Scheduler", "BlockPool",
           "init_kv_pool", "ServeError", "Overloaded", "DeadlineExceeded",
           "RequestCancelled", "RowFailed", "WatchdogTimeout",
           "EngineClosed", "SnapshotCorrupt", "FaultInjector",
           "FaultInjected", "Journal", "JournalReplay", "replay",
           "read_snapshot", "write_snapshot", "JOURNAL_FILE",
           "SNAPSHOT_FILE"]
