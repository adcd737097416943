"""Kernels: the paged decode attention kernel's share of its roofline.
The least time the chip could take for its share of the window's decode
attention — the larger of the live KV bytes over peak HBM bandwidth and
the attention FLOPs over peak bf16 FLOP/s (as the configuration's family
module counts them), every layer, every decoded token, only pages that
hold live tokens — over the kernel's device time (the Pallas call's
operations, ``paged_attention`` in the trace) on the first of the cell's
chips. A mesh shards the pool by KV head, so each chip's share of the
bytes and FLOPs is 1/``chips``; a record without ``chips`` is one chip's.
Notes which bound applies. Silent where no such operation ran."""
import opcount

KERNEL = "paged_attention"


def read(rec):
    t = sum(d for name, _, d in rec["ops"] if name == KERNEL)
    pos = opcount.decoded_positions(rec["requests"], 0.0, rec["window_s"])
    n = rec["counters"]["tokens_out"]
    if t <= 0 or not pos or n <= 0:
        return None
    fam = opcount.family(rec["model"])
    d = fam.dims_of(rec["model"])
    bs = rec["block_size"]
    scale = n * d.L / len(pos) / rec.get("chips", 1)
    flops = scale * sum(fam.attn_flops(d, p) for p in pos)
    nbytes = scale * sum(fam.attn_bytes(d, p, bs) for p in pos)
    t_bw = nbytes / rec["peak"]["hbm_bytes_per_s"]
    t_fl = flops / rec["peak"]["bf16_flops"]
    rec.setdefault("notes", []).append(
        f"paged_decode_roofline: {'bandwidth' if t_bw >= t_fl else 'compute'}"
        f" bound; {nbytes:.6g} bytes, {flops:.6g} FLOPs, kernel {t:.6g} s")
    return 100.0 * max(t_bw, t_fl) / t
