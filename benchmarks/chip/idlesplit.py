"""The chip's idle time in a traced window, split by what the host was
doing, from the program's own spans.

The program marks its phases as ``jax.profiler`` annotations
(``repro.obs.tracing.span``): ``serve.*`` in the serving engine,
``pipe.*`` around each pipeline stage body, ``executor.sleep`` where a
worker parks. ``tracereduce`` keeps every host event of 50 us or more in
``rec["host"]`` as ``(thread, name, start, dur)``, on the trace's clock;
a name may carry the profiler's metadata after a ``#``, so only the part
before it is matched.

Every idle interval of the first chip's operations is split into parts,
taken in this order, each a share of the window:

``sync``     under a ``serve.sync`` or ``serve.window_sync`` span: the
             host blocked on a read-back while the chip had nothing to do;
``host``     under any other ``serve.*`` or ``pipe.*`` span, on any
             thread: host work the chip waits for;
``handoff``  under no such span while a request is in flight (some row of
             ``rec["requests"]`` has ``due <= t < finished``): the time
             between stages — scheduling, a worker's wake-up, the GIL;
``lull``     the rest: no request in flight, the traffic's own pause.

The four add up to ``device_idle_frac`` on one chip. ``split`` returns
None when the window holds no ``serve.*`` span (a program without the
spans reads nothing, not a false 0).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

__all__ = ["split", "union", "intersect", "subtract", "length"]

Intervals = List[Tuple[float, float]]

SYNC = ("serve.sync", "serve.window_sync")
HOST = ("serve.", "pipe.")
SLEEP = "executor.sleep"
#: the pipe that runs on the accelerator domain's worker
ACCEL_PIPE = "pipe.decode"
#: a sleep this long ran into the notifier's 1 s backstop
BACKSTOP_S = 0.9


def union(iv: Intervals) -> Intervals:
    """Sorted, disjoint cover of ``(start, end)`` intervals."""
    out: Intervals = []
    for s, e in sorted(iv):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def intersect(a: Intervals, b: Intervals) -> Intervals:
    """Intersection of two sorted, disjoint interval lists."""
    out: Intervals = []
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: Intervals, b: Intervals) -> Intervals:
    """``a`` less ``b``, both sorted and disjoint."""
    out: Intervals = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def length(iv: Intervals) -> float:
    return sum(e - s for s, e in iv)


def split(rec: Dict) -> Optional[Dict[str, float]]:
    """The window's idle parts as shares of it, plus what the handoff
    note reads: ``handoff_sleep_s``, the handoff under an accelerator
    worker's ``executor.sleep`` (a sleep on a thread that runs
    ``pipe.decode``), and ``backstop_sleeps``, how many of those sleeps
    lasted ``BACKSTOP_S`` or more inside the window."""
    w = rec["trace_window_s"]
    spans = [(name.split("#", 1)[0], thread, s, s + d)
             for thread, name, s, d in rec["host"]]
    if w <= 0 or not any(n.startswith("serve.") for n, _, _, _ in spans):
        return None
    busy = union([(s, s + d) for _, s, d in rec["ops"]])
    idle = subtract([(0.0, w)], busy)
    sync = union([(s, e) for n, _, s, e in spans if n in SYNC])
    host = union([(s, e) for n, _, s, e in spans
                  if n.startswith(HOST) and n not in SYNC])
    flight = union([(r["due"], w if r["finished"] is None else r["finished"])
                    for r in rec["requests"] if r["due"] is not None])
    p_sync = intersect(idle, sync)
    rest = subtract(idle, sync)
    p_host = intersect(rest, host)
    rest = subtract(rest, host)
    p_handoff = intersect(rest, flight)
    lull = subtract(rest, flight)
    accel = {t for n, t, _, _ in spans if n == ACCEL_PIPE}
    sleeps = [(s, e) for n, t, s, e in spans if n == SLEEP and t in accel]
    return {
        "sync": length(p_sync) / w,
        "host": length(p_host) / w,
        "handoff": length(p_handoff) / w,
        "lull": length(lull) / w,
        "handoff_sleep_s": length(intersect(p_handoff, union(sleeps))),
        "backstop_sleeps": sum(1 for s, e in sleeps if e - s >= BACKSTOP_S),
    }
