#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 benchmarks/chip/run_cell.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1> [--control 1] [--save-records PATH]

from the root of a checkout. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``, each number compared beside its limit. Progress, the compile
count of the window and the same checks go to standard error, the checks
last. Without a TPU, with fewer chips than the cell asks for, or outside
a checkout that holds the program's sources, it exits non-zero and prints
no result.

``--control 1`` puts the fp8 control in the program's place in the
check, so the run must come out not ``correct`` (for setting the limit;
the benchmark's own runs leave it off).
``--save-records PATH`` keeps the traced run's reduced records, gzipped.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-records", default=None)
    args = ap.parse_args()
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        log(f"run_cell: no program sources under {src}")
        return 2
    # the compile cache lives at a fixed path inside this checkout, whatever
    # the environment says: the program takes the directory it is given
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # the TPU runtime would otherwise log to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(HERE), str(src)]
    import cellrun
    try:
        out = cellrun.run(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START,
                          control=bool(args.control),
                          save_records=args.save_records, log=log)
    except cellrun.NoChip as e:
        log(f"run_cell: {e}")
        return 1
    except cellrun.CellError as e:
        log(f"run_cell: {e}")
        return 2
    for k, v in out["checks"].items():
        log(f"{k}: {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the engine's executor threads are daemons; leave without waiting on
    # interpreter teardown of device buffers
    os._exit(code)
