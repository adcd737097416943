"""Model step, prefill: device milliseconds per prompt window, from the
trace: the window-0 prompt prefill (``jit__prefill_impl``) with its
scatter into the pool (``jit__scatter_impl``), and the paged
chunked-prefill windows (``jit__prefill_window_impl``), their time in the
window over the number of prefill launches there (a launch cut by the
window's edge counts in part)."""

LAUNCHES = ("jit__prefill_impl", "jit__prefill_window_impl")
PROGRAMS = LAUNCHES + ("jit__scatter_impl",)


def read(rec):
    n = sum(share for name, _, _, share in rec["modules"] if name in LAUNCHES)
    if not n:
        return None
    t = sum(d for name, _, d, _ in rec["modules"] if name in PROGRAMS)
    return t / n * 1e3
