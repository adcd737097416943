"""Operations and bytes of the window's decode work, counted from shapes.

The counts belong to the configuration's model family: the module its
file names under ``family`` (``dense_decoder.py``), found by that name, so
a new family adds a module and edits none here. What every family shares
is here: the family's module, and the positions the window decoded.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

__all__ = ["family", "decoded_positions"]


def family(model: Dict):
    """The family module a configuration file names under ``family``."""
    return importlib.import_module(model["family"])


def decoded_positions(requests: List[Dict], t0: float, t1: float
                      ) -> List[int]:
    """Positions of the tokens decoded between ``t0`` and ``t1``. A
    request's first token comes from its prompt; tokens 2..n are decoded
    at positions ``prompt .. prompt + n - 2``, and are taken to leave at
    even steps between its first token and its finish."""
    out = []
    for r in requests:
        n, first, fin = r.get("out"), r.get("first"), r.get("finished")
        if not n or n < 2 or first is None or fin is None:
            continue
        step = (fin - first) / (n - 1)
        for i in range(1, n):
            t = first + step * i
            if t0 <= t < t1:
                out.append(r["prompt"] + i - 1)
    return out
