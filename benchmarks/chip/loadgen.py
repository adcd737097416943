"""Traffic from a mix file: one general generator for every cell.

A mix is a JSON file under ``traffic/``. Its keys:

``loop``
    ``"closed"``: ``clients`` clients, each sending its next request when
    its previous one returns. ``"open"``: requests sent at fixed due
    times, ``rate_per_s`` on average, whatever the server does.
``prompt`` / ``output``
    token-count distributions, ``{"dist": "uniform", "lo", "hi"}`` or
    ``{"dist": "lognormal", "median", "sigma", "lo", "hi"}``.
``arrival`` (open loop)
    ``{"dist": "gamma", "cv": c}``: inter-arrival gaps with coefficient of
    variation ``c`` (1 is Poisson, larger is burstier).
``lead_s`` (open loop)
    seconds of traffic sent before the window opens, so the window starts
    on a loaded server.
``block`` (closed loop)
    sizes come in blocks of this many requests; an open-loop segment
    (lead-in, window) is one block of its own length.

The seed draws the prompt tokens and nothing else: the sizes and the
arrival times are one fixed draw of the mix, the same for every seed. Each
block holds the distribution's quantiles at ``(i + 0.5) / block``, in an
order drawn once (``SCHEDULE_SEED``); the gaps of an open-loop segment are
one fixed gamma draw, scaled so that the segment's ``round(rate *
seconds)`` requests span exactly its seconds. So every seed offers the
same work at the same times. Were the order shuffled by the seed, which
prompt meets which burst would set the tail: on one TPU v5e that moved
the chat cell's 90th-percentile TTFT by about 11% of its median from seed
to seed, against about 2% between two runs of one seed.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np

__all__ = ["quantiles", "request_sizes", "closed_loop_requests",
           "open_loop_schedule", "prompt_tokens", "nearest_rank"]

#: the fixed draw of every mix's size order and gaps (not the run's seed)
SCHEDULE_SEED = 20040610


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """The ``n`` mid-point quantiles of a size distribution, clipped to
    ``[lo, hi]`` and rounded to whole tokens."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if dist["dist"] == "uniform":
        v = lo + u * (hi + 1 - lo)
        v = np.floor(v)
    elif dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        v = np.round(float(dist["median"]) * np.exp(float(dist["sigma"]) * z))
    else:
        raise ValueError(f"unknown size distribution {dist['dist']!r}")
    return np.clip(v, lo, hi).astype(np.int64)


def request_sizes(mix: Dict, n: int, rng: np.random.Generator,
                  block: int = 0) -> List[Tuple[int, int]]:
    """``n`` (prompt_len, max_new) pairs: whole blocks of the quantile
    sizes (``block``, else the mix's), each block shuffled by ``rng``
    (prompts and outputs apart)."""
    block = block or int(mix.get("block", 16))
    qp = quantiles(mix["prompt"], block)
    qo = quantiles(mix["output"], block)
    out: List[Tuple[int, int]] = []
    while len(out) < n:
        p = rng.permutation(qp)
        o = rng.permutation(qo)
        out.extend((int(a), int(b)) for a, b in zip(p, o))
    return out[:n]


def prompt_tokens(rng: np.random.Generator, length: int,
                  vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=length, dtype=np.int64) \
        .astype(np.int32)


def closed_loop_requests(mix: Dict, seed: int, vocab: int,
                         per_client: int
                         ) -> List[List[Tuple[np.ndarray, int]]]:
    """Each client's queue of (prompt, max_new), ``per_client`` long. The
    queues are dealt round-robin from one list of blocks, so the first
    requests of all clients together hold whole blocks of sizes."""
    clients = int(mix["clients"])
    sizes = request_sizes(mix, clients * per_client,
                          np.random.default_rng(SCHEDULE_SEED))
    rng = np.random.default_rng(seed)
    reqs = [(prompt_tokens(rng, p, vocab), m) for p, m in sizes]
    return [reqs[c::clients] for c in range(clients)]


def _gaps(arrival: Dict, n: int, seconds: float,
          rng: np.random.Generator) -> np.ndarray:
    if arrival["dist"] != "gamma":
        raise ValueError(f"unknown arrival distribution {arrival['dist']!r}")
    shape = 1.0 / float(arrival["cv"]) ** 2
    draw = rng.gamma(shape, 1.0, size=n)
    return draw * (seconds / draw.sum())


def open_loop_schedule(mix: Dict, seed: int, vocab: int, seconds: float
                       ) -> List[Tuple[float, np.ndarray, int, bool]]:
    """(due offset in s, prompt, max_new, in_window) for the lead-in
    segment then the window segment; the window opens at ``lead_s``."""
    sched = np.random.default_rng(SCHEDULE_SEED)
    rng = np.random.default_rng(seed)
    rate = float(mix["rate_per_s"])
    lead = float(mix.get("lead_s", 0.0))
    out = []
    for t0, dur, inwin in ((0.0, lead, False), (lead, float(seconds), True)):
        n = int(round(rate * dur))
        if n == 0:
            continue
        # each segment is one block: its n quantile sizes, whatever the seed
        sizes = request_sizes(mix, n, sched, block=n)
        gaps = _gaps(mix["arrival"], n, dur, sched)
        # the first arrival at the segment's start, each next one a gap
        # later: all n fall inside [t0, t0 + dur)
        due = t0 + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        for t, (p, m) in zip(due, sizes):
            out.append((float(t), prompt_tokens(rng, p, vocab), m, inwin))
    return out


def nearest_rank(values: List[float], q: float) -> float:
    """The ``q`` quantile by nearest rank (an infinite value counts as the
    largest): robust for the few tens of samples a window holds."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(0, math.ceil(q * len(v)) - 1)]
