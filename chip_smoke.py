#!/usr/bin/env python3
"""Serve stablelm-1.6b at its published widths on a TPU, end to end.

    python chip_smoke.py [--seed 0]      # one chip: the serving path
    python chip_smoke.py --chips 4       # the tensor-parallel path only

One chip: random bf16 weights from ``--seed``, a paged KV pool of 4 GiB
(1,366 blocks of 16 tokens), and 16 requests with 256-2048-token prompts
and 64-128 new tokens through ``ServeEngine.submit``/``result`` — the
first few at once, the rest staggered so rows join mid-decode. It checks
that every request returns exactly its tokens, all in the vocabulary,
with no failed request; that the engine reads the pool through the Pallas
kernel and the compiled decode chunk holds the Mosaic call; and that one
decode step's logits on the Pallas path agree with the gather reference
on the chip. ``--chips 4`` instead serves the same prompts on a
``model=4`` mesh and on one chip in this process, and reports whether the
greedy tokens agree (else the first divergence and its logit gap) and how
the pool and weights are spread over the four devices.

Device kind, compile seconds, peak memory, tokens/s and TTFT are printed
as smoke information, not as measurements. The last line of standard
output is ``{"ok": true, "device": {...}}``; without a TPU, or outside a
checkout of this repository, the script exits non-zero and prints no
such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "stablelm-1.6b"
BLOCK = 16
POOL_BLOCKS = 1366            # 1,366 x 3 MiB pages = 4 GiB at stablelm widths
MAX_BATCH = 8
DECODE_CHUNK = 8
#: Pallas-vs-gather logits agreement, relative to the reference's largest
#: |logit|: the two paths round attention differently in bf16 (the kernel
#: accumulates P@V in f32, the reference multiplies bf16 probabilities)
LOGIT_RTOL = 5e-2
#: a tensor-parallel divergence is a bf16 near-tie only if both engines'
#: tokens are within this many logits of an independent reference's best
#: (random weights leave many near-ties among 100k logits)
TIE_GAP = 0.1


class SmokeFailure(Exception):
    """A phase produced a wrong or missing result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def make_requests(rng, n, prompt_lo, prompt_hi, new_lo, new_hi, vocab):
    """``n`` (prompt, max_new) pairs: the extremes of the prompt range are
    always present, so chunked prefill runs from one window to its most."""
    lens = rng.integers(prompt_lo, prompt_hi + 1, size=n)
    lens[0], lens[-1] = prompt_hi, prompt_lo
    news = rng.integers(new_lo, new_hi + 1, size=n)
    return [(rng.integers(0, vocab, size=int(s)).astype("int32"), int(m))
            for s, m in zip(lens, news)]


def serve(eng, reqs, *, burst: int, stagger_s: float):
    """Submit ``burst`` requests at once, wait for a first token, then
    stagger the rest so they join a batch that is mid-decode. Returns the
    futures in submission order."""
    futs = [eng.submit(p, max_new=m) for p, m in reqs[:burst]]
    deadline = time.perf_counter() + 600.0
    while all(f.first_token_at is None for f in futs):
        check(time.perf_counter() < deadline, "no first token in 600 s")
        time.sleep(0.01)
    for p, m in reqs[burst:]:
        time.sleep(stagger_s)
        futs.append(eng.submit(p, max_new=m))
    return futs


def collect(eng, futs, reqs, vocab):
    """Every request returns exactly ``max_new`` in-vocabulary tokens and
    none failed: a failed row raises from ``result``."""
    outs = []
    for i, (f, (_, m)) in enumerate(zip(futs, reqs)):
        toks = eng.result(f, timeout=900.0)
        check(len(toks) == m, f"request {i}: {len(toks)} tokens, wanted {m}")
        check(bool(((toks >= 0) & (toks < vocab)).all()),
              f"request {i}: token outside the vocabulary")
        outs.append([int(t) for t in toks])
    check(eng.stats["row_failures"] == 0,
          f"{eng.stats['row_failures']} row failure(s)")
    return outs


def engine_kwargs(max_seq_len):
    return dict(max_batch=MAX_BATCH, kv_blocks=POOL_BLOCKS, block_size=BLOCK,
                max_seq_len=max_seq_len, decode_chunk=DECODE_CHUNK)


def peak_gib(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


def phase_serve(cfg, params, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.engine import ServeEngine

    rng = np.random.default_rng(seed)
    reqs = make_requests(rng, 16, 256, 2048, 64, 128, cfg.vocab_size)
    max_seq = max(len(p) + m for p, m in reqs)
    with ServeEngine(cfg, params, **engine_kwargs(max_seq)) as eng:
        check(eng.paged_impl == "pallas",
              f"engine reads the pool through {eng.paged_impl!r}, not the "
              "Pallas kernel")
        pool_bytes = eng._pkv.nbytes
        check(pool_bytes >= 4 * 2**30, f"pool is {pool_bytes} bytes < 4 GiB")
        print(f"pool: {POOL_BLOCKS} blocks x {BLOCK} tokens, shape "
              f"{tuple(eng._pkv.shape)}, {pool_bytes / 2**30:.3f} GiB; "
              f"paged_impl={eng.paged_impl}", flush=True)

        # warm-up: one request compiles every program the run uses (the
        # window-0 prefill, the pool scatter, the prefill window, the
        # decode chunk and the table/carry updates)
        t0 = time.perf_counter()
        p, _ = make_requests(rng, 1, 300, 300, 16, 16, cfg.vocab_size)[0]
        eng.result(eng.submit(p, max_new=16), timeout=900.0)
        print(f"compile + warm-up: {time.perf_counter() - t0:.1f} s",
              flush=True)

        t0 = time.perf_counter()
        futs = serve(eng, reqs, burst=4, stagger_s=0.05)
        collect(eng, futs, reqs, cfg.vocab_size)
        wall = time.perf_counter() - t0
        ntok = sum(m for _, m in reqs)
        ttft = sorted(f.ttft for f in futs)
        print(f"served {len(reqs)} requests, {ntok} tokens in {wall:.2f} s: "
              f"{ntok / wall:.1f} tok/s; TTFT median "
              f"{ttft[len(ttft) // 2]:.3f} s, max {ttft[-1]:.3f} s; "
              f"stats {eng.stats}", flush=True)

        B = MAX_BATCH
        z = jnp.zeros((B,), jnp.int32)
        hlo = eng._decode_paged.lower(
            eng.params, eng._pkv, eng._tables_dev, z, z, z,
            n=eng.decode_chunk).compile().as_text()
        check("tpu_custom_call" in hlo,
              "compiled decode chunk holds no tpu_custom_call")
        print("decode chunk: tpu_custom_call present", flush=True)
    print(f"peak_bytes_in_use: {peak_gib(jax.devices()[0])}", flush=True)


def phase_logits(cfg, params, seed: int) -> None:
    """One decode step on a small pool: Pallas path vs gather reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm
    from repro.serve.kvcache import init_kv_pool, scatter_prefill_rows

    B, nb = MAX_BATCH, 20
    S = nb * BLOCK
    rng = np.random.default_rng(seed + 1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    _, cache = jax.jit(functools.partial(lm.prefill, cfg))(params, toks)
    blocks = 1 + np.arange(B * nb, dtype=np.int32).reshape(B, nb)
    pool = jax.jit(scatter_prefill_rows)(
        init_kv_pool(cfg, B * nb + 1, BLOCK), jnp.asarray(blocks),
        cache["k"], cache["v"])
    del cache
    tables = jnp.asarray(blocks)
    # ragged per-row positions, the last at the end of its 20th page
    lengths = jnp.asarray(np.arange(1, B + 1) * (S - 1) // B, jnp.int32)
    token = jnp.asarray(rng.integers(0, cfg.vocab_size, B), jnp.int32)
    active = jnp.ones((B,), bool)
    got = {}
    for impl in ("pallas", "gather"):
        step = jax.jit(functools.partial(lm.decode_step_paged, cfg,
                                         impl=impl))
        got[impl] = np.asarray(
            step(params, pool, tables, lengths, token, active)[0])
    ref, out = got["gather"], got["pallas"]
    check(bool(np.isfinite(out).all()), "Pallas logits not finite")
    err = float(np.max(np.abs(out - ref)))
    scale = float(np.max(np.abs(ref)))
    agree = int((out.argmax(-1) == ref.argmax(-1)).sum())
    print(f"logits pallas vs gather: max |diff| {err:.4g} (tolerance "
          f"{LOGIT_RTOL} x max |ref| = {LOGIT_RTOL * scale:.4g}); argmax "
          f"agrees on {agree}/{B} rows", flush=True)
    check(err <= LOGIT_RTOL * scale, "Pallas logits outside tolerance")


def phase_mesh(cfg, params, seed: int) -> None:
    """The tensor-parallel engine on a model=4 mesh against the one-chip
    engine, same prompts, same process."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_ctx, small_mesh
    from repro.models import lm
    from repro.serve.engine import ServeEngine

    rng = np.random.default_rng(seed)
    reqs = make_requests(rng, 8, 256, 1024, 64, 64, cfg.vocab_size)
    max_seq = max(len(p) + m for p, m in reqs)
    outs = {}
    for name, ctx in (("one-chip", None),
                      ("mesh", make_ctx(small_mesh(data=1, model=4)))):
        t0 = time.perf_counter()
        with ServeEngine(cfg, params, ctx=ctx,
                         **engine_kwargs(max_seq)) as eng:
            check(eng.paged_impl == "pallas",
                  f"{name}: paged_impl {eng.paged_impl!r}")
            futs = serve(eng, reqs, burst=4, stagger_s=0.05)
            outs[name] = collect(eng, futs, reqs, cfg.vocab_size)
            print(f"{name}: served {len(reqs)} requests in "
                  f"{time.perf_counter() - t0:.1f} s (compile included)",
                  flush=True)
            if ctx is not None:
                pool_devs = {s.device.id: s.data.nbytes
                             for s in eng._pkv.addressable_shards}
                w_devs: dict = {}
                for leaf in jax.tree_util.tree_leaves(eng.params):
                    for s in leaf.addressable_shards:
                        w_devs[s.device.id] = (w_devs.get(s.device.id, 0)
                                               + s.data.nbytes)
                in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
                          for d in jax.devices()}
                print(f"mesh pool bytes per device: {pool_devs} "
                      f"(whole pool {eng._pkv.nbytes})", flush=True)
                print(f"mesh weight bytes per device: {w_devs}", flush=True)
                print(f"bytes_in_use per device: {in_use}", flush=True)
                check(len(pool_devs) == 4 and all(
                    b * 4 == eng._pkv.nbytes for b in pool_devs.values()),
                    "pool is not split evenly over four devices")
                check(len(w_devs) == 4 and max(w_devs.values())
                      < sum(w_devs.values()) / 2,
                      "weights are not spread over four devices")
        gc.collect()

    one, mesh = outs["one-chip"], outs["mesh"]
    same = sum(a == b for a, b in zip(one, mesh))
    print(f"greedy tokens: {same}/{len(one)} requests identical on the mesh "
          f"and on one chip", flush=True)
    div = [(i, next(k for k in range(len(one[i])) if one[i][k] != mesh[i][k]))
           for i in range(len(one)) if one[i] != mesh[i]]
    if not div:
        return
    # judge every first divergence by an independent reference, the
    # contiguous prefill on one chip over the shared history: both tokens
    # must be within a bf16 near-tie of its best. One padded batch, so one
    # compile; causal attention ignores the padding after each history.
    hist = [np.concatenate([reqs[i][0], np.asarray(one[i][:t], np.int32)])
            for i, t in div]
    width = max(len(h) for h in hist)
    toks = np.zeros((len(hist), width), np.int32)
    for r, h in enumerate(hist):
        toks[r, :len(h)] = h
    last = np.asarray([len(h) - 1 for h in hist], np.int32)
    logits, _ = jax.jit(functools.partial(lm.prefill, cfg))(
        params, jnp.asarray(toks), last_positions=jnp.asarray(last))
    lg = np.asarray(logits)
    worst = 0.0
    for r, (i, t) in enumerate(div):
        best = float(lg[r].max())
        g_one = best - float(lg[r, one[i][t]])
        g_mesh = best - float(lg[r, mesh[i][t]])
        worst = max(worst, g_one, g_mesh)
        print(f"  request {i}: first divergence at step {t}: one-chip "
              f"{one[i][t]} vs mesh {mesh[i][t]}; reference logit gap to "
              f"its best: one-chip {g_one:.4g}, mesh {g_mesh:.4g}",
              flush=True)
    check(worst < TIE_GAP,
          f"a divergence is not a near-tie (gap {worst:.4g} >= {TIE_GAP})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and requests")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-parallel path and its "
                         "one-chip comparison")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repository sources under {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices; found {len(devs)}", file=sys.stderr)
        return 1
    print(f"device: {devs[0].device_kind} x {len(devs)}; compile cache "
          f"{cache_dir}", flush=True)

    from repro.configs import get_config
    from repro.models import lm

    cfg = dataclasses.replace(get_config(ARCH), param_dtype="bfloat16")
    t0 = time.perf_counter()
    params = jax.jit(functools.partial(lm.init_params, cfg))(
        jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    print(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.num_heads} heads / {cfg.num_kv_heads} KV heads, head_dim "
          f"{cfg.hd}, vocab {cfg.vocab_size}; bf16 weights "
          f"{nbytes / 2**30:.3f} GiB in {time.perf_counter() - t0:.1f} s",
          flush=True)
    try:
        if args.chips == 4:
            phase_mesh(cfg, params, args.seed)
        else:
            phase_serve(cfg, params, args.seed)
            gc.collect()
            phase_logits(cfg, params, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
