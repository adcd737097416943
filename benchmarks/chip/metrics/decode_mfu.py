"""Model step, the whole decode step's share of the cell's peak: the model
FLOPs of the tokens decoded in the window (every layer's matmuls and the
head, and attention at each token's live length, as the configuration's
family module counts them) over the decode-chunk program's device time in
the window, on the first of the cell's chips, times the cell's chips times
the peak bf16 FLOP/s. Useful FLOPs over the aggregate peak: work that a
mesh repeats on every chip (the replicated head) counts once. A record
without ``chips`` is one chip's. Tokens are the engine's decode counter
over the window; their positions come from the window's requests."""
import opcount

PROGRAM = "jit__decode_paged_impl"


def read(rec):
    t = sum(d for name, _, d, _ in rec["modules"] if name == PROGRAM)
    pos = opcount.decoded_positions(rec["requests"], 0.0, rec["window_s"])
    n = rec["counters"]["tokens_out"]
    if t <= 0 or not pos or n <= 0:
        return None
    fam = opcount.family(rec["model"])
    d = fam.dims_of(rec["model"])
    flops = n * sum(fam.decode_flops(d, p) for p in pos) / len(pos)
    chips = rec.get("chips", 1)
    return 100.0 * flops / (t * chips * rec["peak"]["bf16_flops"])
