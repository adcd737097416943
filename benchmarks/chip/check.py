"""Whether a run served correctly: what decides ``correct``.

After the window has closed and every request is back:

* every request the run sent has returned, with exactly ``max_new``
  tokens, each inside the vocabulary;
* the engine's decode-token counter, plus one first token per request,
  equals the tokens the requests returned (exactly, when nothing was
  preempted: a preempted row decodes its tokens again);
* on a sample of the returned requests drawn from the seed, with the
  longest in it, the configuration's float32 reference (the module its
  file names under ``reference``, here ``reference.py``) is run once over
  each prompt and its served tokens; the widest gap by which a served
  token's reference logit lies below the reference's best at its
  position must stay within the configuration's ``max_logit_gap``. All
  tokens the engine serves are greedy, so a sound run's gap is bf16
  rounding at near-ties; a wrong token shows as a gap of the logits'
  own spread.

``control=True`` puts the fp8 control (the reference's first choice in
fp8, ``reference.py``) in the program's place: its gap on the same sample
is the one compared, so the run must come out not ``correct``. The
program's own gap on that sample is logged beside it.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

import numpy as np

__all__ = ["sample", "check_run"]


def sample(records, seed: int, served_tokens: int, max_requests: int
           ) -> List:
    """Returned requests to compare: the longest (prompt plus served
    tokens), then others in an order drawn from the seed, until the
    sample holds ``served_tokens`` served tokens or ``max_requests``."""
    done = [r for r in records if r.out is not None and len(r.out)]
    if not done:
        return []
    done.sort(key=lambda r: -(len(r.prompt) + len(r.out)))
    rest = done[1:]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    picked, n = [done[0]], len(done[0].out)
    for i in order:
        if n >= served_tokens or len(picked) >= max_requests:
            break
        picked.append(rest[i])
        n += len(rest[i].out)
    return picked


def check_run(conf: Dict, seed: int, records, stats: Dict, *,
              tok_start: int, stuck: bool, control: bool = False) -> Dict:
    vocab = int(conf["vocab_size"])
    lim = conf["check"]
    sent = [r for r in records if r.req is not None]
    missing = sum(1 for r in sent if r.out is None)
    bad = sum(1 for r in sent if r.out is not None and (
        len(r.out) != r.max_new or r.out.min() < 0 or r.out.max() >= vocab))
    returned = sum(len(r.out) for r in sent if r.out is not None)
    firsts = sum(1 for r in sent if r.req.first_token_at is not None)
    counted = stats["tokens_out"] - tok_start + firsts
    preempted = stats.get("preempted", 0)
    count_ok = counted == returned if preempted == 0 else counted >= returned

    picked = sample(records, seed, int(lim["served_tokens"]),
                    int(lim["max_requests"]))
    gap = low = None
    if picked:
        ref = importlib.import_module(conf["reference"])
        g, gl = ref.reference_gaps(
            conf, seed, [(r.prompt, r.out) for r in picked],
            control=control)
        gap = float(g.max())
        low = float(gl.max()) if control else None
    limit = float(lim["max_logit_gap"])
    compared = low if control else gap
    checks = {
        "requests_missing": {"value": missing, "limit": 0},
        "requests_wrong_length": {"value": bad, "limit": 0},
        "tokens_counted": {"value": counted, "limit": returned},
        "logit_gap": {"value": compared, "limit": limit},
    }
    correct = (not stuck and missing == 0 and bad == 0 and count_ok
               and compared is not None and compared <= limit)
    lines = [f"sample: {len(picked)} requests, "
             f"{sum(len(r.out) for r in picked)} served tokens, longest "
             f"{max([len(r.prompt) + len(r.out) for r in picked] or [0])}"]
    if control:
        lines.append(f"fp8 control in the program's place; the program's "
                     f"own logit_gap: {gap!r}")
    lines += [f"check {k}: {v['value']!r} (limit {v['limit']!r})"
              for k, v in checks.items()]
    lines.append(f"correct: {correct}")
    return {"correct": correct, "checks": checks, "lines": lines}
