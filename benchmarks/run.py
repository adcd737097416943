# One function per paper table. Prints ``name,value,derived`` CSV and
# writes a BENCH_<suite>.json trajectory file per suite.
"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig9,...] [--quick]
                                            [--bench-dir DIR]

table2  — task/edge creation overheads (paper Table 2)
fig9    — random-DAG runtime/memory vs baselines (paper Figure 9)
fig11   — co-run throughput + utilization (paper Figure 11)
fig13   — LSDNN inference (paper Figure 13, §5.3)
fig17   — conditional-vs-unrolled memory (paper Figure 17 memory panel)
fig21   — incremental timing propagation (paper Figure 21, §5.5)
roofline— the dry-run roofline table (§Roofline), from results/dryrun.jsonl
pipeline— task-parallel pipeline throughput vs hand-rolled loop
          (Pipeflow follow-up, arXiv:2202.00717); honors --quick
serve   — continuous-batching engine under Poisson arrivals vs the
          per-call baseline (tokens/sec, p50/p99 latency); honors --quick;
          --prefix-share swaps in the shared-prefix workload (cold vs
          warm prefix cache over one trace: hit-rate, tokens saved,
          admission/TTFT p50/p99 deltas)
serve_slo — SLO-aware overload control: tier-0 tail TTFT uncontended vs
          under a tier-1 best-effort flood (shedding, queue-deadline
          expiry, cost-model preemption); honors --quick
serve_mesh — tensor-parallel sharded serving: --mesh-model N KV-head-
          sharded engine vs single-device over one trace (bit-exact
          parity asserted; per-device pool bytes ~1/N; admission
          capacity at fixed device memory); honors --quick. Needs N
          devices and fails with fewer — when only serve_mesh is
          selected the harness forces CPU host devices itself, otherwise
          set XLA_FLAGS=--xla_force_host_platform_device_count=N up front
paged_decode — gather-free paged decode read path vs the gather oracle
          across pool occupancies; honors --quick
journal_gate — durability overhead gate: serve tok/s with the request
          WAL attached must stay within REPRO_JOURNAL_GATE_BUDGET
          (default 5%) of detached; honors --quick

Each completed suite drops ``BENCH_<suite>.json`` into --bench-dir
(default: CWD): the run config, every emitted row, the well-known
metrics (``tok_per_s`` / ``p50_ms`` / ``p99_ms`` where a suite reports
them), and provenance (git sha + ISO-8601 UTC timestamp) — the
machine-readable perf trajectory that used to exist only as stdout CSV.
The serve and serve_slo suites also write their run's
Chrome trace-event JSON (``TRACE_<suite>.json``, Perfetto-loadable)
alongside.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import traceback
from datetime import datetime, timezone

#: row-name suffix -> trajectory metric key (suite-agnostic extraction)
_METRIC_SUFFIXES = ("tok_per_s", "p50_ms", "p99_ms")


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip()
    except OSError:
        return ""


def _write_trajectory(bench_dir: str, suite: str, config: dict,
                      rows: list, elapsed_s: float) -> str:
    metrics = {}
    for name, val, _ in rows:
        for suffix in _METRIC_SUFFIXES:
            if name.endswith(suffix):
                try:
                    metrics[name] = float(val)
                except ValueError:
                    pass
    payload = {
        "suite": suite,
        "config": config,
        "git_sha": _git_sha(),
        "timestamp": time.time(),
        "timestamp_iso": datetime.now(timezone.utc).isoformat(),
        "elapsed_s": round(elapsed_s, 3),
        "rows": [{"name": n, "value": v, "derived": d} for n, v, d in rows],
        "metrics": metrics,
    }
    os.makedirs(bench_dir, exist_ok=True)
    path = os.path.join(bench_dir, f"BENCH_{suite}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--quick", action="store_true",
                    help="seconds-scale smoke sizes (tier-1 environment)")
    ap.add_argument("--bench-dir", default=".",
                    help="where BENCH_<suite>.json trajectory files land")
    ap.add_argument("--prompt-dist", default="choice",
                    choices=("choice", "lognormal"),
                    help="serve suite prompt-length distribution "
                         "(lognormal = heavy tail)")
    ap.add_argument("--prefix-share", action="store_true",
                    help="serve suite: shared-prefix workload, cold vs "
                         "warm prefix cache over one trace")
    ap.add_argument("--mesh-model", type=int, default=2, metavar="N",
                    help="serve_mesh suite: width of the mesh 'model' "
                         "axis (default 2)")
    args = ap.parse_args()

    only_pre = [s for s in args.only.split(",") if s]
    if only_pre == ["serve_mesh"]:
        # the mesh suite needs N devices and jax reads XLA_FLAGS exactly
        # once at backend init — safe to force here only when no other
        # suite shares the process
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.mesh_model}").strip()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (fig9_micro_random_dag, fig11_corun_throughput,
                   fig13_lsdnn, fig17_conditional_memory,
                   fig21_incremental_timing, journal_overhead_gate,
                   paged_decode_microbench, pipeline_throughput,
                   roofline_report, serve_continuous, serve_slo,
                   table2_task_overhead)

    # trace artifacts land next to the BENCH_*.json they belong to
    os.makedirs(args.bench_dir, exist_ok=True)

    def _serve_mesh_rows():
        import jax
        if jax.device_count() < args.mesh_model:
            raise RuntimeError(
                f"serve_mesh needs {args.mesh_model} devices, found "
                f"{jax.device_count()} (on the CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={args.mesh_model})")
        return serve_continuous.bench_mesh(quick=args.quick,
                                           mesh_model=args.mesh_model)

    def _trace(suite: str) -> str:
        return os.path.join(args.bench_dir, f"TRACE_{suite}.json")

    suites = {
        "table2": lambda: table2_task_overhead.bench(200_000),
        "fig9": fig9_micro_random_dag.bench,
        "fig11": fig11_corun_throughput.bench,
        "fig13": fig13_lsdnn.bench,
        "fig17": fig17_conditional_memory.bench,
        "fig21": fig21_incremental_timing.bench,
        "roofline": roofline_report.bench,
        "pipeline": lambda: pipeline_throughput.bench(quick=args.quick),
        "serve": lambda: (
            serve_continuous.bench_prefix_share(
                quick=args.quick, trace_path=_trace("serve"))
            if args.prefix_share else
            serve_continuous.bench(
                quick=args.quick, prompt_dist=args.prompt_dist,
                trace_path=_trace("serve"))),
        "serve_slo": lambda: serve_slo.bench(
            quick=args.quick, trace_path=_trace("serve_slo")),
        "serve_mesh": lambda: _serve_mesh_rows(),
        "paged_decode":
            lambda: paged_decode_microbench.bench(quick=args.quick),
        "journal_gate":
            lambda: journal_overhead_gate.bench(quick=args.quick),
    }
    config = {"quick": args.quick, "only": args.only,
              "prompt_dist": args.prompt_dist,
              "prefix_share": args.prefix_share,
              "mesh_model": args.mesh_model,
              "mesh_model_env": os.environ.get("REPRO_MESH_MODEL", ""),
              "paged_impl_env": os.environ.get("REPRO_PAGED_IMPL", ""),
              "async_decode_env": os.environ.get("REPRO_ASYNC_DECODE", ""),
              "journal_gate_budget_env":
                  os.environ.get("REPRO_JOURNAL_GATE_BUDGET", "")}
    only = [s for s in args.only.split(",") if s]
    failures = 0
    for name, fn in suites.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            rows = []
            for row_name, val, derived in fn():
                rows.append((row_name, val, derived))
                print(f"{row_name},{val},{derived}", flush=True)
            elapsed = time.time() - t0
            path = _write_trajectory(args.bench_dir, name, config, rows,
                                     elapsed)
            print(f"# {name} done in {elapsed:.1f}s -> {path}", flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == '__main__':
    main()
