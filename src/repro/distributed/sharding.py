"""Sharding rules: map logical tensor axes onto the production mesh.

Logical axes used by the model code:
    ``dp``   — data-parallel axes for the batch dim (``("pod","data")`` on
               the multi-pod mesh, ``("data",)`` single-pod)
    ``fsdp`` — parameter/optimizer sharding axis (ZeRO-3 style), = "data"
    ``tp``   — tensor/expert-parallel axis, = "model"
    ``sp``   — sequence-parallel axis for long-context KV caches, = "model"

The model calls :func:`constrain` on activations; :func:`param_specs`
assigns a PartitionSpec to every parameter by path-based rules (Megatron
column/row pattern for attention/MLP, expert-dim sharding for MoE, inner-dim
sharding for Mamba). Everything degrades to no-ops when no mesh is active so
the same model code runs single-device smoke tests unchanged.
"""
from __future__ import annotations

import contextlib
import dataclasses
import re
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ShardCtx", "use_shard_ctx", "current_ctx", "constrain",
           "param_specs", "named_sharding", "logical_to_spec",
           "gather_tp", "manual_serve_map", "serve_tp_size",
           "serve_attn_sharded", "serve_mlp_sharded", "serve_param_specs",
           "serve_param_shardings", "serve_pool_spec", "serve_kv_cache_spec",
           "MeshDivisibilityError", "validate_serve_mesh"]


@dataclass
class ShardCtx:
    mesh: Optional[Mesh]
    dp: Tuple[str, ...] = ("data",)
    #: fsdp may span multiple mesh axes (("pod","data") on the multi-pod
    #: mesh, so parameter/optimizer state scales with TOTAL chips)
    fsdp: Optional[Any] = "data"
    tp: Optional[str] = "model"
    sp: Optional[str] = "model"
    #: True while tracing inside a shard_map body: shapes are per-shard,
    #: with_sharding_constraint is illegal, and gather_tp becomes live
    manual: bool = False

    def axis_size(self, logical: str) -> int:
        if self.mesh is None:
            return 1

        def tup(x):
            if x is None:
                return ()
            return x if isinstance(x, tuple) else (x,)

        names = {"dp": self.dp, "fsdp": tup(self.fsdp),
                 "tp": tup(self.tp), "sp": tup(self.sp)}[logical]
        size = 1
        for n in names:
            size *= self.mesh.shape[n]
        return size


_local = threading.local()


def current_ctx() -> Optional[ShardCtx]:
    return getattr(_local, "ctx", None)


@contextlib.contextmanager
def use_shard_ctx(ctx: Optional[ShardCtx]):
    prev = current_ctx()
    _local.ctx = ctx
    try:
        yield ctx
    finally:
        _local.ctx = prev


def logical_to_spec(ctx: ShardCtx, logical: Sequence[Any]) -> P:
    """Translate ('dp', None, 'tp') style logical specs to a PartitionSpec."""
    out = []
    for a in logical:
        if a is None:
            out.append(None)
        elif a == "dp":
            out.append(ctx.dp if len(ctx.dp) > 1 else ctx.dp[0])
        elif a == "fsdp":
            out.append(ctx.fsdp)
        elif a in ("tp", "sp"):
            out.append(getattr(ctx, a))
        else:  # raw mesh axis name
            out.append(a)
    return P(*out)


def constrain(x: Any, *logical: Any) -> Any:
    """with_sharding_constraint under the active ShardCtx (no-op without).

    Inside a shard_map body (``ctx.manual``) constraints are illegal —
    shardings there are determined by the in/out specs — so this degrades
    to identity and :func:`gather_tp` takes over at the hand-off points.
    """
    ctx = current_ctx()
    if ctx is None or ctx.mesh is None or ctx.manual:
        return x
    spec = logical_to_spec(ctx, logical)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def named_sharding(ctx: ShardCtx, *logical: Any) -> NamedSharding:
    return NamedSharding(ctx.mesh, logical_to_spec(ctx, logical))


# --------------------------------------------------------------------------- #
# parameter sharding rules
# --------------------------------------------------------------------------- #

def _divisible(dim: int, ctx: ShardCtx, axis) -> bool:
    if axis is None or ctx.mesh is None:
        return False
    axes = axis if isinstance(axis, tuple) else (axis,)
    size = 1
    for a in axes:
        size *= ctx.mesh.shape[a]
    return dim % size == 0


def _rule(path: str, shape: Tuple[int, ...], ctx: ShardCtx) -> P:
    """PartitionSpec for one parameter (path is '/'-joined key names)."""
    tp, fsdp = ctx.tp, ctx.fsdp
    leaf = path.rsplit("/", 1)[-1]

    def guard(spec_axes):
        """Drop mesh axes that do not divide the dim (uneven shards)."""
        fixed = []
        for dim, ax in zip(shape, spec_axes):
            fixed.append(ax if _divisible(dim, ctx, ax) else None)
        return P(*fixed)

    if leaf in ("embed", "pos_embed"):
        return guard((tp, fsdp))                       # (V, D)
    if leaf == "lm_head":
        return guard((fsdp, tp))                       # (D, V)
    if leaf in ("wq", "wk", "wv", "wi", "wg", "in_proj", "dt_proj",
                "shared_wi", "shared_wg", "dense_wi", "dense_wg",
                "fused_proj"):
        return guard((fsdp, tp))                       # column parallel
    if leaf in ("wo", "wd", "out_proj", "shared_wd", "dense_wd"):
        return guard((tp, fsdp))                       # row parallel
    if leaf in ("bq", "bk", "bv"):
        return guard((tp,))
    if leaf == "router":
        return guard((fsdp, None))                     # (D, E)
    if leaf in ("e_wi", "e_wg"):                       # (E, D, F)
        if _divisible(shape[0], ctx, tp):
            return guard((tp, fsdp, None))
        return guard((None, fsdp, tp))
    if leaf == "e_wd":                                 # (E, F, D)
        if _divisible(shape[0], ctx, tp):
            return guard((tp, None, fsdp))
        return guard((None, tp, fsdp))
    if leaf in ("conv_w", "conv_b", "x_proj", "A_log", "ssm_D", "dt_bias",
                "ssm_norm"):
        return guard((tp,) + (None,) * (len(shape) - 1))  # (dI, ...)
    # norms / scalars / biases: replicated
    return P(*([None] * len(shape)))


def param_specs(params: Any, ctx: ShardCtx, stacked_prefixes=("blocks",)) -> Any:
    """Tree of PartitionSpec matching ``params``; arrays under a stacked
    prefix (scan-over-layers) get a leading unsharded layer dim."""

    def visit(path_keys, leaf):
        path = "/".join(str(getattr(k, "key", k)) for k in path_keys)
        shape = tuple(np.shape(leaf))
        stacked = any(path.startswith(p) for p in stacked_prefixes)
        if stacked:
            spec = _rule(path, shape[1:], ctx)
            return P(*((None,) + tuple(spec)))
        return _rule(path, shape, ctx)

    return jax.tree_util.tree_map_with_path(visit, params)


# --------------------------------------------------------------------------- #
# serve-time tensor parallelism (exact-bit, shard_map manual mode)
# --------------------------------------------------------------------------- #
# The serve data plane shards the paged KV pool by KV head over the
# ``model`` mesh axis and runs decode/prefill steps under shard_map. To
# keep greedy streams BIT-IDENTICAL to the single-device oracle, the
# parallelism is "exact-bit": every projection weight is sharded on its
# OUTPUT-column dim (wq/wk/wv/bq/bk/bv on heads; wo/wd on d_model columns;
# wi/wg on d_ff columns), so every contraction runs over an UNSHARDED dim
# and each shard's output is a bitwise column-slice of the single-device
# result. Shards are reassembled with tiled all-gathers — pure bit
# concatenation, no arithmetic — so no floating-point reassociation can
# perturb the stream (a psum-of-partials in bf16 flips ~37% of output
# elements; see docs/sharded_serving.md). The collectives are tiny
# activation-sized all-gathers; the pool itself is never gathered, which
# hlo_analysis-based CI tests enforce.

class MeshDivisibilityError(ValueError):
    """Model-axis size does not divide the head/feature counts it shards."""


#: leaves sharded on their last (output-column) dim under serve TP
_SERVE_ATTN_LEAVES = frozenset({"wq", "wk", "wv", "bq", "bk", "bv", "wo"})
_SERVE_MLP_LEAVES = frozenset({"wi", "wg", "wd"})


def serve_tp_size(ctx: Optional[ShardCtx]) -> int:
    """Size of the tensor-parallel mesh axis (1 when no mesh is active)."""
    if ctx is None or ctx.mesh is None or ctx.tp is None:
        return 1
    return ctx.axis_size("tp")


def serve_attn_sharded(cfg: Any, mp: int) -> bool:
    """True when the attention cluster (and thus the KV pool) shards mp-way."""
    if mp <= 1 or cfg.ssm or cfg.hybrid_attn_every:
        return False
    return (cfg.num_kv_heads % mp == 0 and cfg.num_heads % mp == 0
            and cfg.d_model % mp == 0)


def serve_mlp_sharded(cfg: Any, mp: int) -> bool:
    """True when the dense-MLP cluster shards mp-way (MoE experts never do)."""
    if mp <= 1 or cfg.ssm or cfg.hybrid_attn_every:
        return False
    return cfg.d_ff % mp == 0 and cfg.d_model % mp == 0


def validate_serve_mesh(cfg: Any, mp: int) -> None:
    """Raise :class:`MeshDivisibilityError` for head counts mp can't shard.

    SSM/hybrid architectures serve fully replicated on any mesh size, so
    only attention architectures are constrained.
    """
    if mp <= 1 or cfg.ssm or cfg.hybrid_attn_every:
        return
    if not serve_attn_sharded(cfg, mp):
        raise MeshDivisibilityError(
            f"{cfg.name}: mesh model axis {mp} must divide num_kv_heads="
            f"{cfg.num_kv_heads}, num_heads={cfg.num_heads} and d_model="
            f"{cfg.d_model} to shard the KV pool by head; pick a divisor "
            "or run single-device")


def serve_param_specs(cfg: Any, params: Any, ctx: ShardCtx) -> Any:
    """PartitionSpec tree for serve TP: output-column sharding only.

    Every sharded leaf gets ``P(..., tp)`` on its LAST dim (rank-derived,
    so stacked ``blocks`` leaves need no special casing); everything else
    — embed, lm_head, norms, routers, MoE experts, SSM state — stays
    replicated so per-shard compute is bitwise identical.
    """
    mp = serve_tp_size(ctx)
    attn_ok = serve_attn_sharded(cfg, mp)
    mlp_ok = serve_mlp_sharded(cfg, mp)

    def visit(path_keys, leaf):
        keys = [str(getattr(k, "key", k)) for k in path_keys]
        ndim = np.ndim(leaf)
        name = keys[-1] if keys else ""
        in_blocks = bool(keys) and keys[0] == "blocks"
        sharded = in_blocks and (
            (attn_ok and name in _SERVE_ATTN_LEAVES)
            or (mlp_ok and name in _SERVE_MLP_LEAVES))
        if sharded:
            return P(*([None] * (ndim - 1) + [ctx.tp]))
        return P(*([None] * ndim))

    return jax.tree_util.tree_map_with_path(visit, params)


def serve_param_shardings(cfg: Any, params: Any, ctx: ShardCtx) -> Any:
    """NamedSharding tree matching :func:`serve_param_specs`."""
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(ctx.mesh, spec),
        serve_param_specs(cfg, params, ctx),
        is_leaf=lambda x: isinstance(x, P))


def serve_pool_spec(cfg: Any, ctx: ShardCtx) -> P:
    """Spec for the paged pool (N, L, 2, R, bs, W): packed KV-head rows
    sharded (``init_kv_pool`` packs per shard, so rows never straddle)."""
    if serve_attn_sharded(cfg, serve_tp_size(ctx)):
        return P(None, None, None, ctx.tp, None, None)
    return P(None, None, None, None, None, None)


def serve_kv_cache_spec(cfg: Any, ctx: ShardCtx) -> P:
    """Spec for contiguous prefill caches k/v (L, B, KV, S, hd): KV sharded."""
    if serve_attn_sharded(cfg, serve_tp_size(ctx)):
        return P(None, None, ctx.tp, None, None)
    return P(None, None, None, None, None)


def gather_tp(x: Any, axis: int = -1) -> Any:
    """Reassemble per-shard output columns: tiled all-gather along ``axis``.

    Live only inside a shard_map body under serve TP (``ctx.manual``);
    identity otherwise. Tiled all-gather concatenates the shards' bits in
    mesh order — no arithmetic — which is what makes the sharded decode
    bit-exact vs the single-device oracle.
    """
    ctx = current_ctx()
    if (ctx is None or ctx.mesh is None or not ctx.manual
            or ctx.tp is None or ctx.mesh.shape[ctx.tp] == 1):
        return x
    return jax.lax.all_gather(x, ctx.tp, axis=axis % x.ndim, tiled=True)


def manual_serve_map(fn, ctx: ShardCtx, in_specs, out_specs):
    """shard_map ``fn`` over ``ctx.mesh`` with the manual ShardCtx active.

    ``check_vma=False`` because replicated outputs (sampled tokens, carry)
    are produced by identical per-shard compute on gathered — bitwise
    identical — operands, which the varying-axes checker cannot see.
    """
    mctx = dataclasses.replace(ctx, manual=True)

    def body(*args):
        with use_shard_ctx(mctx):
            return fn(*args)

    return jax.shard_map(body, mesh=ctx.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
