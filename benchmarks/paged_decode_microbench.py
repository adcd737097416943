# Decode-step microbench for the paged read path (ROADMAP production-serve
# goal; not a paper figure). Occupancy sweep: gather-free reads vs the
# materializing gather oracle.
"""Paged decode-attention read path across pool occupancies.

The gather oracle pays O(capacity) per row per step — it materializes and
attends over ``max_blocks * block_size`` positions regardless of the rows'
true lengths. The gather-free paths (``repro.kernels.paged_attention``)
bound their work by ``max(lengths)``, so their cost follows *occupancy*:

* ``paged_read_*``  — the XLA traced-bound page loop (the off-TPU serve
  default) at low / mid / full occupancy, with the gather oracle timed on
  identical inputs. The derived column reports the speedup; low occupancy
  (short rows in a large pool) is where paging pays.
* ``pallas_interpret_read_low_occ_ms`` — the Pallas kernel through the
  interpreter, for the trajectory record only: per-grid-step interpreter
  overhead dominates on CPU (it is a correctness tool here; the Mosaic
  lowering on TPU is the perf path).
* ``decode_step_*`` — end-to-end ``lm.decode_step_paged`` (all layers,
  projections, MLP) at low occupancy, paged vs gather read path.

``--chip`` times, on a TPU only, the Mosaic-compiled Pallas kernel against
the XLA page loop per layer call at the chip benchmark cells' attention
shapes (``CHIP_SHAPES``): 8 rows, a 128-column table of 16-token pages,
bf16 pages, rows spread around the cell traffic's mean live length::

    PYTHONPATH=src python benchmarks/paged_decode_microbench.py --chip
"""
from __future__ import annotations

import functools
import time
from typing import Iterator, Tuple


def _time_ms(fn, iters: int) -> float:
    fn().block_until_ready()                    # warm-up / compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e3


def bench(quick: bool = False) -> Iterator[Tuple[str, str, str]]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.kernels.ops import paged_attention
    from repro.kernels.paged_attention import kv_pack
    from repro.models import lm
    from repro.serve.kvcache import gather_read_attention, init_kv_pool

    B, H, KV, hd = 8, 8, 4, 64
    bs = 16
    mb = 16 if quick else 64                    # capacity per row
    iters = 20 if quick else 100
    N = B * mb + 1
    cap = mb * bs

    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    q = jax.random.normal(ks[0], (B, H, hd))
    P = kv_pack(KV, hd)
    pool_kv = jax.random.normal(ks[1], (N, 1, 2, KV // P, bs, P * hd))
    tables = jnp.asarray(
        1 + np.arange(B * mb, dtype=np.int32).reshape(B, mb))

    gather_read = jax.jit(gather_read_attention)  # the shared oracle

    occupancies = [("low", bs - 1), ("mid", cap // 2 - 1),
                   ("full", cap - 1)]
    for occ_name, pos in occupancies:
        lengths = jnp.full((B,), pos, jnp.int32)
        t_paged = _time_ms(
            lambda: paged_attention(q, pool_kv, tables, lengths, 0,
                                    impl="xla"), iters)
        t_gather = _time_ms(
            lambda: gather_read(q, pool_kv, 0, tables, lengths), iters)
        yield (f"paged_read_{occ_name}_occ_ms", f"{t_paged:.3f}",
               f"{t_gather/t_paged:.2f}x_gather")
        yield (f"gather_read_{occ_name}_occ_ms", f"{t_gather:.3f}", "")

    # Pallas interpreter datapoint (trajectory record; see module docstring)
    lengths = jnp.full((B,), bs - 1, jnp.int32)
    t_pallas = _time_ms(
        lambda: paged_attention(q, pool_kv, tables, lengths, 0,
                                impl="pallas"), max(2, iters // 10))
    yield ("pallas_interpret_read_low_occ_ms", f"{t_pallas:.3f}",
           "interpret_mode")

    # end-to-end decode step at low occupancy (smoke model: all layers)
    cfg = get_config("stablelm-1.6b").smoke()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    mb2 = 32                            # large pool: the short rows below
    #                                     sit at ~3% of per-row capacity
    N2 = B * mb2 + 1
    pool = init_kv_pool(cfg, N2, bs)
    tables2 = jnp.asarray(
        1 + np.arange(B * mb2, dtype=np.int32).reshape(B, mb2))
    lengths2 = jnp.full((B,), bs - 1, jnp.int32)
    token = jnp.ones((B,), jnp.int32)
    active = jnp.ones((B,), bool)
    times = {}
    for impl in ("xla", "gather"):
        step = jax.jit(functools.partial(lm.decode_step_paged, cfg,
                                         impl=impl))
        times[impl] = _time_ms(
            lambda: step(params, pool, tables2, lengths2, token, active)[0],
            max(5, iters // 4))
    yield ("decode_step_paged_low_occ_ms", f"{times['xla']:.3f}",
           f"{times['gather']/times['xla']:.2f}x_gather")
    yield ("decode_step_gather_low_occ_ms", f"{times['gather']:.3f}", "")


#: the chip cells' decode attention: (config, query heads, KV heads,
#: head_dim, mean live length of the cell's traffic in tokens)
CHIP_SHAPES = (("stablelm-1.6b", 32, 32, 64, 230),
               ("mistral-nemo-12b", 32, 8, 128, 550))
CHIP_B, CHIP_BLOCK, CHIP_MAX_BLOCKS = 8, 16, 128
#: layers in the timed pool and calls per timed program (layer i % L)
CHIP_LAYERS, CHIP_CALLS = 4, 24


def chip_case(H: int, KV: int, hd: int, mean_len: int, seed: int = 0):
    """Inputs of one layer call at a chip shape: bf16 queries and pool,
    every row's pages scattered over the pool, row lengths spread evenly
    from half to one and a half times ``mean_len``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attention import kv_pack

    B, bs, mb = CHIP_B, CHIP_BLOCK, CHIP_MAX_BLOCKS
    P = kv_pack(KV, hd)
    N = B * mb + 1
    kq, kp = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.normal(kq, (B, H, hd), jnp.bfloat16)
    pool = jax.random.normal(
        kp, (N, CHIP_LAYERS, 2, KV // P, bs, P * hd), jnp.bfloat16)
    rng = np.random.default_rng(seed)
    tables = jnp.asarray(
        1 + rng.permutation(B * mb).astype(np.int32).reshape(B, mb))
    lengths = jnp.asarray(
        np.rint(mean_len * np.linspace(0.5, 1.5, B)).astype(np.int32))
    return q, pool, tables, lengths


def time_layer_calls(attn, q, pool, tables, lengths, reps: int = 20):
    """Time per layer call of ``attn(q, pool, tables, lengths, layer)``
    in ms: host clock around one program of ``CHIP_CALLS`` chained calls
    (each output is the next query) up to ``block_until_ready``, so one
    dispatch spreads over them; median of ``reps``."""
    import statistics
    import jax

    @jax.jit
    def chain(q, pool, tables, lengths):
        return jax.lax.fori_loop(
            0, CHIP_CALLS,
            lambda i, x: attn(x, pool, tables, lengths, i % CHIP_LAYERS), q)

    args = (q, pool, tables, lengths)       # arguments, not constants
    chain(*args).block_until_ready()        # compile and warm up
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        chain(*args).block_until_ready()
        times.append((time.perf_counter() - t0) * 1e3 / CHIP_CALLS)
    return statistics.median(times)


def chip_bench(reps: int = 20) -> Iterator[Tuple[str, str, str]]:
    import jax
    import jax.numpy as jnp
    from repro.kernels.ops import on_tpu
    from repro.kernels.paged_attention import paged_attention

    if not on_tpu():
        raise SystemExit("--chip times the Mosaic kernel: it needs a TPU")
    dev = jax.devices()[0]
    yield ("device", dev.device_kind, f"{jax.device_count()}_devices")
    for name, H, KV, hd, mean_len in CHIP_SHAPES:
        q, pool, tables, lengths = chip_case(H, KV, hd, mean_len)
        impls = {
            impl: functools.partial(paged_attention, impl=impl,
                                    interpret=False)
            for impl in ("pallas", "xla")}
        out = {impl: f(q, pool, tables, lengths, 0).astype(jnp.float32)
               for impl, f in impls.items()}
        gap = float(jnp.max(jnp.abs(out["pallas"] - out["xla"])))
        t = {impl: time_layer_calls(f, q, pool, tables, lengths, reps)
             for impl, f in impls.items()}
        yield (f"chip_{name}_len{mean_len}_pallas_ms", f"{t['pallas']:.5f}",
               f"{t['xla'] / t['pallas']:.2f}x_xla")
        yield (f"chip_{name}_len{mean_len}_xla_ms", f"{t['xla']:.5f}", "")
        yield (f"chip_{name}_len{mean_len}_max_abs_diff", f"{gap:.3g}",
               "pallas_vs_xla")


if __name__ == "__main__":
    import sys
    rows = chip_bench() if "--chip" in sys.argv[1:] else bench(quick=True)
    for name, val, derived in rows:
        print(f"{name},{val},{derived}", flush=True)
