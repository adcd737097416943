"""Model step, decode: device milliseconds per decode step, from the
trace: the decode-chunk program's (``jit__decode_paged_impl``) time in the
window over the steps it ran there (each run is one chunk of
``decode_chunk`` steps; a run cut by the window's edge counts in part)."""

PROGRAM = "jit__decode_paged_impl"


def read(rec):
    runs = [(d, share) for name, _, d, share in rec["modules"]
            if name == PROGRAM]
    steps = sum(share for _, share in runs) * rec["decode_chunk"]
    if not steps:
        return None
    return sum(d for d, _ in runs) / steps * 1e3
