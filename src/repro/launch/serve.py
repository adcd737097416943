"""Serving launcher: the resident continuous-batching engine.

Requests are submitted one by one against the long-running pipeline
(``submit()``/``result()``); with ``--stagger`` the submissions arrive
spaced out, so later requests join the batch while earlier ones are
mid-decode — the continuous-batching path, for every architecture
(attention models page their KV; SSM/hybrid models slot their recurrent
state). ``--per-call`` runs the retired per-call grouped pipeline for
comparison.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b \
        --preset smoke --batch 4 --prompt-len 32 --max-new 32 --stagger 0.05

Observability (:mod:`repro.obs`): ``--stats-interval N`` prints a one-line
runtime summary every N seconds (tok/s, queue depth, resident rows, pool
occupancy, preempt/stall counts, TTFT p50); ``--trace PATH`` writes a
Chrome trace-event JSON of the run — open it at https://ui.perfetto.dev
or ``chrome://tracing`` to see every request's lifecycle on its slot
track next to the engine-cycle and pipeline-line tracks:

    PYTHONPATH=src python -m repro.launch.serve \
        --stats-interval 1 --trace out.json

Durability (``docs/robustness.md``): ``--state-dir DIR`` journals every
request transition to ``DIR/journal.wal`` and recovers on startup —
incomplete requests from a previous crash replay bit-identically, and a
prior ``engine.snap`` warm-starts the prefix cache (a corrupt snapshot
falls back cold, typed). SIGTERM triggers a graceful drain
(``--drain-deadline`` bounds it: past the deadline residents are
checkpoint-preempted), then a snapshot + journal flush, then close:

    PYTHONPATH=src python -m repro.launch.serve --state-dir /var/lib/repro
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import signal
import threading
import time

import jax
import numpy as np

from ..configs import get_config
from ..distributed.sharding import validate_serve_mesh
from ..models import lm
from ..obs import Observability, StatsLogger
from ..serve.engine import SNAPSHOT_FILE, ServeEngine
from .compile_cache import enable_compile_cache
from .mesh import make_ctx, small_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b",
                    help="model architecture (default: the quick smoke "
                         "workload's stablelm-1.6b)")
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"],
                    help="smoke: the reduced CPU-sized config; full: the "
                         "published widths with bf16 weights, TPU only")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--decode-chunk", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prompt tokens per chunked-prefill window "
                         "(default: decode_chunk * block_size)")
    ap.add_argument("--kv-blocks", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--stagger", type=float, default=0.0,
                    help="seconds between submissions (0 = all at once)")
    ap.add_argument("--async-decode", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="async decode lookahead: device-resident carry + "
                         "one-chunk dispatch pipelining. Unset defers to "
                         "REPRO_ASYNC_DECODE; --no-async-decode forces the "
                         "synchronous reference path even with the env set")
    ap.add_argument("--per-call", action="store_true",
                    help="use the generate() batch-call shim instead of "
                         "submit/result")
    ap.add_argument("--priority", type=int, default=0,
                    help="scheduling tier for the submitted requests "
                         "(0 = highest/SLO tier; larger = best-effort)")
    ap.add_argument("--deadline", type=float, default=None, metavar="S",
                    help="per-request deadline in seconds (expired "
                         "requests fail typed DeadlineExceeded)")
    ap.add_argument("--tier-target", action="append", default=None,
                    metavar="TIER=SHARE",
                    help="guaranteed minimum admission share for a tier "
                         "under sustained higher-tier load (repeatable, "
                         "e.g. --tier-target 1=0.25)")
    ap.add_argument("--shed-budget", type=float, default=None, metavar="S",
                    help="load-shedding queue-wait budget (seconds, all "
                         "tiers): submit() raises Overloaded when the "
                         "estimated wait exceeds it. Unset defers to "
                         "REPRO_SHED_BUDGET_S")
    ap.add_argument("--watchdog", type=float, default=None, metavar="S",
                    help="engine watchdog budget: fail all futures typed "
                         "WatchdogTimeout when a busy engine makes no "
                         "progress for S seconds. Unset defers to "
                         "REPRO_WATCHDOG_S")
    ap.add_argument("--fault-inject", default=None, metavar="SPEC",
                    help="deterministic fault-injection spec (see "
                         "repro.serve.faultinject), e.g. "
                         "'grow_fail:p=0.05,seed=11'. Unset defers to "
                         "REPRO_FAULT_INJECT")
    ap.add_argument("--mesh-model", type=int, default=None, metavar="N",
                    help="shard the serve data plane N-way over the mesh "
                         "'model' axis (KV-head-partitioned pool + "
                         "tensor-parallel decode). N must divide the "
                         "model's KV heads, heads and d_model; on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count"
                         "=N first. Unset defers to REPRO_MESH_MODEL")
    ap.add_argument("--state-dir", default=None, metavar="DIR",
                    help="durability state directory: journal every "
                         "request transition to DIR/journal.wal, recover "
                         "(replay incomplete requests + warm-start the "
                         "prefix cache from DIR/engine.snap) on startup, "
                         "and snapshot on graceful shutdown/SIGTERM")
    ap.add_argument("--drain-deadline", type=float, default=10.0,
                    metavar="S",
                    help="graceful-drain budget on SIGTERM: residents get "
                         "S seconds to finish before being "
                         "checkpoint-preempted (default 10)")
    ap.add_argument("--fsync-every", type=int, default=1, metavar="N",
                    help="journal fsync cadence: every N records (1 = "
                         "maximal durability, 0 = only at flush/close)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stats-interval", type=float, default=None,
                    help="print a one-line runtime stats summary every N "
                         "seconds (implies observability on)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON (Perfetto/"
                         "chrome://tracing) of the run (implies "
                         "observability on)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.preset == "smoke":
        cfg = cfg.smoke()
    else:
        # published widths are served from bf16 weights (training keeps its
        # f32 masters) and only on the chip: a JAX that did not find the
        # TPU would otherwise carry on, slowly, on the CPU
        platform = jax.devices()[0].platform
        if platform != "tpu":
            raise SystemExit(f"--preset full needs a TPU; JAX found "
                             f"{platform!r}")
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16")
    if cfg.frontend != "none":
        print(f"note: {cfg.name} uses a stub frontend; serving the text "
              "backbone only")
    ctx = None
    if args.mesh_model is not None and args.mesh_model > 1:
        # typed MeshDivisibilityError on KV-head counts the axis can't
        # divide — refuse up front rather than shard a lopsided pool
        validate_serve_mesh(cfg, args.mesh_model)
        ctx = make_ctx(small_mesh(data=1, model=args.mesh_model))
        print(f"mesh: model axis = {args.mesh_model} "
              f"({jax.device_count()} devices)")

    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=args.prompt_len)
               .astype(np.int32) for _ in range(args.batch)]
    total_new = args.batch * args.max_new

    obs = Observability() \
        if (args.stats_interval is not None or args.trace) else None
    logger = None
    if args.stats_interval is not None:
        logger = StatsLogger(obs.metrics, interval=args.stats_interval)

    tier_targets = None
    if args.tier_target:
        tier_targets = {}
        for spec in args.tier_target:
            tier, _, share = spec.partition("=")
            tier_targets[int(tier)] = float(share)

    with ServeEngine(cfg, params, ctx=ctx, decode_chunk=args.decode_chunk,
                     prefill_chunk=args.prefill_chunk,
                     kv_blocks=args.kv_blocks,
                     block_size=args.block_size,
                     async_decode=args.async_decode,
                     tier_targets=tier_targets,
                     shed_budget_s=args.shed_budget,
                     watchdog_s=args.watchdog,
                     fault_inject=args.fault_inject,
                     obs=obs) as eng:
        if args.preset == "full" and eng.paged and eng.paged_impl != "pallas":
            raise SystemExit(f"--preset full serves through the Pallas "
                             f"paged kernel, not {eng.paged_impl!r} (unset "
                             f"REPRO_PAGED_IMPL)")
        replayed = {}
        if args.state_dir:
            # crash/restart recovery: warm-start from a prior snapshot
            # (typed cold fallback on corruption) and re-submit every
            # journal-incomplete request — greedy decode replays them
            # bit-identically — then journal this run at the same path
            replayed = eng.recover(args.state_dir,
                                   fsync_every=args.fsync_every)
            if replayed or eng.stats["warm_started"]:
                print(f"recovered: {len(replayed)} incomplete request(s) "
                      f"replaying ({eng.stats['replayed_tokens']} prompt "
                      f"tokens), {eng.stats['warm_started']} warm prefix "
                      f"node(s)")

        def _graceful(signum, frame):
            # runs the drain off the signal frame: the main thread may be
            # blocked in result(), and drain/snapshot must not run there
            def run():
                print(f"SIGTERM: draining "
                      f"(deadline {args.drain_deadline:.1f}s)")
                eng.drain(deadline_s=args.drain_deadline)
                if args.state_dir:
                    path = os.path.join(args.state_dir, SNAPSHOT_FILE)
                    n = eng.snapshot(path)
                    print(f"snapshot: {n} bytes -> {path}")
                eng.close()
                os._exit(0)
            threading.Thread(target=run, name="serve-drain",
                             daemon=True).start()
        signal.signal(signal.SIGTERM, _graceful)

        if logger is not None:
            logger.start()
        t0 = time.time()
        if args.per_call:
            # the retired per-call grouped pipeline, kept as the baseline
            outs = eng._generate_grouped(prompts, args.max_new)
        else:
            # every arch serves through the resident pipeline now: paged KV
            # for attention models, the slot-state pool for SSM/hybrid
            reqs = []
            for p in prompts:
                reqs.append(eng.submit(p, max_new=args.max_new,
                                       priority=args.priority,
                                       deadline_s=args.deadline))
                if args.stagger:
                    time.sleep(args.stagger)
            outs = [eng.result(r, timeout=600.0) for r in reqs]
        for r in replayed.values():
            eng.result(r, timeout=600.0)
        dt = time.time() - t0
        print(f"{cfg.name}: generated {total_new} tokens in {dt:.2f}s "
              f"({total_new/dt:.1f} tok/s, batch={args.batch}, "
              f"mode={'per-call' if args.per_call else 'continuous'})")
        print("engine stats:", eng.stats)
        print("sample:", outs[0][:16].tolist())
        if args.state_dir:
            # clean exit: settle and leave a warm snapshot for the next run
            eng.drain(deadline_s=args.drain_deadline)
            n = eng.snapshot(os.path.join(args.state_dir, SNAPSHOT_FILE))
            print(f"snapshot: {n} bytes -> "
                  f"{os.path.join(args.state_dir, SNAPSHOT_FILE)}")
        if logger is not None:
            logger.stop()
    if args.trace:
        obs.export(args.trace)
        print(f"trace written to {args.trace} "
              f"({len(obs.tracer)} spans; open at https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
