"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis carries pure data parallelism across ICI-disconnected pods (DCN),
so only gradient all-reduces cross it.

A FUNCTION (not a module constant) so importing this module never touches
jax device state — the dry-run sets XLA_FLAGS before first jax init.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh

from ..distributed.sharding import ShardCtx

__all__ = ["make_production_mesh", "make_ctx", "small_mesh"]


def _make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_ctx(mesh: Optional[Mesh]) -> ShardCtx:
    """ShardCtx with dp covering (pod,) data axes."""
    if mesh is None:
        return ShardCtx(mesh=None)
    names = mesh.axis_names
    dp = tuple(n for n in ("pod", "data") if n in names)
    # fsdp spans the pod axis too: parameter/optimizer shards scale with
    # TOTAL chips (512 on the 2-pod mesh), which is what makes >100B
    # configs trainable at all
    fsdp = dp if "data" in names else None
    if fsdp is not None and len(fsdp) == 1:
        fsdp = fsdp[0]
    return ShardCtx(mesh=mesh, dp=dp or ("data",),
                    fsdp=fsdp,
                    tp="model" if "model" in names else None,
                    sp="model" if "model" in names else None)


def small_mesh(data: int = 2, model: int = 2) -> Mesh:
    """Reduced mesh for tests (requires enough local/virtual devices)."""
    return _make_mesh((data, model), ("data", "model"))
