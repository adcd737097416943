"""The profiler trace of a window, reduced to what the metric readers read.

``Profile`` records a ``jax.profiler`` trace around the window and marks
the window itself with a host ``TraceAnnotation``, so the window's bounds
are on the trace's own clock. ``records_from_file`` reads the trace back
(``jax.profiler.ProfileData``, nothing outside JAX) into plain lists,
clipped to the window, in seconds from its start:

``ops``      ``(op, start, dur)`` of every operation on the first of the
             cell's chips' ``XLA Ops`` line; ``op`` is the HLO
             instruction's name without its number (``paged_attention``
             for the Pallas kernel, ``fusion``, ``all-gather``, ``while``
             for a loop that holds other operations);
``modules``  ``(program, start, dur, share)`` of every program run on that
             chip's ``XLA Modules`` line, ``program`` as jit names it
             (``jit__decode_paged_impl``), ``share`` the part of the run
             that lies inside the window;
``host``     ``(thread, name, start, dur)`` of host events of 50 us or
             more: the program's spans (``serve.*``, ``pipe.*``) and the
             runtime's own events; ``thread`` is the line's name and
             index. The Python tracer is off, so no frame is recorded
             (the committed records under ``testdata/`` predate that and
             hold frames, ``$file:line function``);
``busy_s``   union of the operations' intervals, averaged over the cell's
             chips;
``trace_window_s`` the window's length on the trace clock;
``chips``    the cell's chips: the device planes of the lowest indices
             (``jax.devices()[:chips]``); a host's other chips, idle in a
             smaller cell, are left out. Readers take a record without
             it, such as those under ``testdata/``, as one chip.

``breakdown`` turns records into the result's ``breakdown``.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import re
import shutil
from pathlib import Path
from typing import Dict, List, Optional, Tuple

__all__ = ["WINDOW", "Profile", "records_from_file", "reduce_window",
           "union_s", "breakdown", "op_name", "save_records", "load_records"]

WINDOW = "benchmark_window"
HOST_MIN_NS = 50_000
#: operations that only hold others (their time is their body's)
CONTAINERS = ("while", "conditional", "call")
#: the engine's decode stage: the thread that runs it is the one whose
#: events say what kept the device waiting. It is found by its span, or
#: in traces recorded with the Python tracer, by its frame
ENGINE_MARKS = ("pipe.decode", "_st_decode")


class Profile:
    """``start`` the profiler ahead of the window, ``open_window`` when it
    opens, ``stop`` when it closes."""

    def __init__(self, directory: Path):
        self.dir = Path(directory)
        self._ann = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        # no Python tracer: a frame per call slows the host it measures and
        # swells the trace; the program's spans name what the host does
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)

    def open_window(self) -> None:
        import jax
        self._ann = jax.profiler.TraceAnnotation(WINDOW)
        self._ann.__enter__()

    def stop(self) -> None:
        import jax
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def path(self) -> str:
        found = sorted(glob.glob(str(self.dir / "plugins" / "profile" / "*"
                                     / "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no trace under {self.dir}")
        return found[-1]

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def op_name(hlo: str) -> str:
    """``%paged_attention.11 = bf16[...] custom-call(...)`` ->
    ``paged_attention``."""
    return hlo.split(" = ", 1)[0].lstrip("%").split(".", 1)[0]


def program_name(module: str) -> str:
    """``jit__decode_paged_impl(1424...)`` -> ``jit__decode_paged_impl``."""
    return module.split("(", 1)[0]


def _device_index(name: str) -> Optional[int]:
    m = re.match(r"/device:[A-Z]+:(\d+)$", name)
    return int(m.group(1)) if m else None


def union_s(intervals: List[Tuple[float, float]]) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def records_from_file(path: str, chips: int) -> Dict:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    w0 = w1 = None
    host_raw = []
    devices: Dict[int, Dict[str, list]] = {}
    for plane in pd.planes:
        di = _device_index(plane.name)
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                thread = f"{line.name or 'thread'}#{i}"
                for e in line.events:
                    if e.name == WINDOW:
                        w0, w1 = e.start_ns, e.start_ns + e.duration_ns
                    elif e.duration_ns >= HOST_MIN_NS:
                        host_raw.append((thread, e.name, e.start_ns,
                                         e.duration_ns))
        elif di is not None:
            d = devices.setdefault(di, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    d["ops"] += [(op_name(e.name), e.start_ns, e.duration_ns)
                                 for e in line.events]
                elif line.name == "XLA Modules":
                    d["modules"] += [(program_name(e.name), e.start_ns,
                                      e.duration_ns) for e in line.events]
    if w0 is None:
        raise ValueError(f"{path}: no {WINDOW!r} host event")
    return reduce_window(w0, w1, host_raw, devices, chips)


def reduce_window(w0: int, w1: int, host_raw: List, devices: Dict,
                  chips: int) -> Dict:
    """The records of the window ``[w0, w1)`` (trace nanoseconds) from the
    host events and each device plane's ``ops`` and ``modules``, keyed by
    device index, of which the ``chips`` lowest belong to the cell."""
    def clip(evs, share=False):
        out = []
        for ev in evs:
            *head, s, dur = ev
            a, b = max(s, w0), min(s + dur, w1)
            if b > a:
                row = (*head, (a - w0) * 1e-9, (b - a) * 1e-9)
                out.append(row + ((b - a) / dur,) if share else row)
        return out

    cell = [devices[i] for i in sorted(devices)[:chips]]
    busy = [union_s([(s, s + d) for _, s, d in clip(dv["ops"])])
            for dv in cell]
    first = cell[0] if cell else {"ops": [], "modules": []}
    return {"ops": clip(first["ops"]),
            "modules": clip(first["modules"], share=True),
            "host": clip(host_raw),
            "busy_s": sum(busy) / len(busy) if busy else 0.0,
            "trace_window_s": (w1 - w0) * 1e-9, "n_devices": len(cell),
            "chips": chips}


def _program_at(modules, starts, t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= modules[i][1] + modules[i][2]:
        return modules[i][0]
    return "no program"


def breakdown(rec: Dict, top: int = 10) -> Dict[str, list]:
    """The device operations that took most time, by program and operation
    (loops that only hold other operations left out), and the longest idle
    gaps, each named by the innermost host event that covers at least half
    of it, on the thread that runs the engine's decode stage."""
    modules = sorted(rec["modules"], key=lambda m: m[1])
    starts = [m[1] for m in modules]
    per: Dict[str, float] = {}
    for name, s, dur in rec["ops"]:
        if name in CONTAINERS:
            continue
        key = f"{_program_at(modules, starts, s)}:{name}"
        per[key] = per.get(key, 0.0) + dur
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    busy = sorted((s, s + d) for _, s, d in rec["ops"])
    gaps, t = [], 0.0
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if rec["trace_window_s"] > t:
        gaps.append((t, rec["trace_window_s"]))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    engine = {h[0] for h in rec["host"]
              if any(m in h[1] for m in ENGINE_MARKS)}
    host = [h for h in rec["host"] if h[0] in engine] if engine \
        else rec["host"]
    named = []
    for a, b in gaps:
        best, best_d = "no host event", None
        for thread, name, s, d in host:
            if min(b, s + d) - max(a, s) >= 0.5 * (b - a) and \
                    (best_d is None or d < best_d):
                best, best_d = f"{name} [{thread}]", d
        named.append([best, b - a])
    return {"device_ops": [[n, v] for n, v in ops], "idle_gaps": named}


def save_records(rec: Dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(rec, f)


def load_records(path: str) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)
