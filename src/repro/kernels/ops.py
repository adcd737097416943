"""Jit'd public wrappers for the Pallas kernels.

The one place that derives a kernel's execution mode from the platform:
Mosaic lowering on TPU, the Pallas interpreter (correctness validation)
everywhere else. The kernel entry points themselves take ``interpret`` as
a required keyword, so no caller can fall into the interpreter on the chip
by omission.

:func:`default_paged_impl` resolves which paged decode-attention read path
the serve engine uses (see ``paged_attention.py``): the ``REPRO_PAGED_IMPL``
environment variable (``pallas`` | ``xla`` | ``gather``) wins, otherwise
``pallas`` (Mosaic) on TPU and ``xla`` (the traced-bound page loop — the
interpreter's per-step overhead makes the Pallas kernel a correctness tool,
not a fast path, off-TPU) everywhere else. ``gather`` is the original
materialize-then-mask reference oracle in ``repro.models.attention``.
"""
from __future__ import annotations

import os

import jax

from .flash_attention import flash_attention as _flash
from .lsdnn_layer import lsdnn_layer as _lsdnn
from .mamba_scan import mamba_scan as _mamba_scan
from .paged_attention import paged_attention as _paged

__all__ = ["flash_attention", "mamba_scan", "lsdnn_layer", "paged_attention",
           "default_paged_impl", "on_tpu"]

PAGED_IMPLS = ("pallas", "xla", "gather")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_paged_impl() -> str:
    env = os.environ.get("REPRO_PAGED_IMPL", "").strip().lower()
    if env:
        if env not in PAGED_IMPLS:
            raise ValueError(
                f"REPRO_PAGED_IMPL={env!r}: expected one of {PAGED_IMPLS}")
        return env
    return "pallas" if on_tpu() else "xla"


def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, prune: bool = True):
    return _flash(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                  prune=prune, interpret=not on_tpu())


def paged_attention(q, pool_kv, tables, lengths, layer, impl: str = "pallas"):
    return _paged(q, pool_kv, tables, lengths, layer, impl=impl,
                  interpret=not on_tpu())


def mamba_scan(dt, x, Bc, Cc, A, block_d: int = 512, chunk: int = 128):
    return _mamba_scan(dt, x, Bc, Cc, A, block_d=block_d, chunk=chunk,
                       interpret=not on_tpu())


def lsdnn_layer(y, w, b, cap: float = 32.0, **blocks):
    return _lsdnn(y, w, b, cap=cap, interpret=not on_tpu(), **blocks)
