"""Sharding: the share of the decode-chunk program's device time
(``jit__decode_paged_impl``) taken by collective operations on the first
of the cell's chips: all-gathers, all-reduces, reduce-scatters, collective
permutes and all-to-alls (their ``-start`` and ``-done`` halves too), each
by its own device time on the ``XLA Ops`` line, which is the part not
hidden behind compute. An operation counts where it starts inside a run
of that program. Notes the operations found and their seconds. Silent
where no collective ran in that program."""
import bisect

PROGRAM = "jit__decode_paged_impl"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")


def read(rec):
    runs = sorted((s, s + d) for name, s, d, _ in rec["modules"]
                  if name == PROGRAM)
    starts = [s for s, _ in runs]
    per = {}
    for name, s, d in rec["ops"]:
        if not name.startswith(COLLECTIVES):
            continue
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            per[name] = per.get(name, 0.0) + d
    total = sum(e - s for s, e in runs)
    if not per or total <= 0:
        return None
    found = ", ".join(f"{n} {v:.6g} s" for n, v in sorted(per.items()))
    rec.setdefault("notes", []).append(
        f"collective_frac: {found}, of {total:.6g} s of {PROGRAM}")
    return sum(per.values()) / total
