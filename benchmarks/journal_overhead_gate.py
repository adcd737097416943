# Journal overhead gate (durability goal, not a paper figure): the
# request WAL must be effectively free when attached — and literally one
# `is None` check per transition when it is not.
"""Serve throughput with the request journal ON vs OFF, gated to a budget.

The durability discipline (:mod:`repro.serve.journal`) journals per
request *transition* — submit/admit/first_token/finish — never per
token, so a saturated decode workload should pay almost nothing for it.
This gate proves that: ONE engine runs an identical workload with a
journal attached (``fsync_every=0`` — buffered writes, fsync off the
hot path, matching what a deployment amortizing durability would run;
fsync cost is a disk property, not engine overhead) and detached, and
the attached-path tokens/sec must stay within budget of the detached
path.

Repetitions are INTERLEAVED off/on and each mode is scored by its BEST
repetition — deterministic per-transition work survives into the
cleanest rep, shared-container CPU throttling does not. Both modes run
the SAME compiled programs (``ServeEngine.set_journal`` rebinds at
idle; journaling never changes compiled shapes).

Budget: the ``REPRO_JOURNAL_GATE_BUDGET`` env var (fraction, default
0.05 — journal appends hit the filesystem).
"""
from __future__ import annotations

import os
import tempfile
import time
from typing import Iterator, Tuple


def _run(eng, prompts, max_new: int) -> float:
    for k in eng.stats:
        eng.stats[k] = 0
    t0 = time.perf_counter()
    reqs = [eng.submit(p, max_new) for p in prompts]
    for r in reqs:
        eng.result(r, timeout=600.0)
    return time.perf_counter() - t0


def bench(quick: bool = False) -> Iterator[Tuple[str, str, str]]:
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve.engine import ServeEngine
    from repro.serve.journal import Journal

    budget = float(os.environ.get("REPRO_JOURNAL_GATE_BUDGET", "0.05"))
    cfg = get_config("stablelm-1.6b").smoke()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    chunk = 4
    n_req = 6
    max_new = 64 if quick else 128
    reps = 5 if quick else 8
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=8).astype(np.int32)
               for _ in range(n_req)]
    total_tokens = n_req * max_new

    samples = {"off": [], "on": []}
    with tempfile.TemporaryDirectory() as td, \
            ServeEngine(cfg, params, decode_chunk=chunk, max_batch=8,
                        kv_blocks=224, block_size=8, prefill_chunk=16,
                        max_seq_len=-(-(8 + max_new) // 8) * 8) as eng:
        # warm-up compiles every program both modes run (identical: the
        # journal is pure python off the device path)
        _run(eng, prompts, max(2, chunk + 1))
        for i in range(reps):
            for mode in ("off", "on"):
                if mode == "on":
                    eng.set_journal(Journal(
                        os.path.join(td, f"rep{i}.wal"), fsync_every=0))
                else:
                    eng.set_journal(None)
                dt = _run(eng, prompts, max_new)
                samples[mode].append(total_tokens / dt)
        eng.set_journal(None)
    off = float(np.max(samples["off"]))
    on = float(np.max(samples["on"]))
    ratio = on / off
    yield ("journal_gate_off_tok_per_s", f"{off:.1f}", f"best_of_{reps}")
    yield ("journal_gate_on_tok_per_s", f"{on:.1f}", f"{ratio:.3f}x_off")
    yield ("journal_gate_overhead_frac", f"{max(0.0, 1.0 - ratio):.4f}",
           f"budget_{budget:.2f}")
    if ratio < 1.0 - budget:
        raise AssertionError(
            f"journal overhead gate failed: journaled path at "
            f"{on:.1f} tok/s vs plain {off:.1f} tok/s "
            f"({(1.0 - ratio) * 100:.1f}% > {budget * 100:.0f}% budget)")
    yield ("journal_gate", "ok", f"within_{budget * 100:.0f}pct")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    for name, val, derived in bench(quick=args.quick):
        print(f"{name},{val},{derived}")
