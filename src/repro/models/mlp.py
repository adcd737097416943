"""Feed-forward layers: SwiGLU (gated) and ungated GELU or squared-ReLU
(``relu(x @ wi) ** 2``, nemotron-h) variants."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import constrain, gather_tp
from .layers import dense_init, dtype_of

__all__ = ["init_mlp", "mlp"]


def init_mlp(key, cfg: ModelConfig, d_ff: int = 0,
             prefix: str = "") -> Dict[str, jnp.ndarray]:
    """prefix in {"", "shared_", "dense_"} distinguishes the qwen2-moe shared
    experts and the arctic dense-residual path in the sharding rules."""
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    pdt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    if cfg.mlp_gated:
        return {prefix + "wi": dense_init(ks[0], (D, F), pdt),
                prefix + "wg": dense_init(ks[1], (D, F), pdt),
                prefix + "wd": dense_init(ks[2], (F, D), pdt)}
    return {prefix + "wi": dense_init(ks[0], (D, F), pdt),
            prefix + "wd": dense_init(ks[2], (F, D), pdt)}


def mlp(p, x, cfg: ModelConfig, prefix: str = "") -> jnp.ndarray:
    cdt = dtype_of(cfg.compute_dtype)
    wi = p[prefix + "wi"].astype(cdt)
    wd = p[prefix + "wd"].astype(cdt)
    if cfg.mlp_act == "swiglu":
        wg = p[prefix + "wg"].astype(cdt)
        h = jax.nn.silu(x @ wg) * (x @ wi)
    elif cfg.mlp_act == "relu2":
        h = jnp.square(jax.nn.relu(x @ wi))
    elif cfg.mlp_act == "gelu":
        h = jax.nn.gelu(x @ wi)
    else:
        raise ValueError(f"unknown mlp_act {cfg.mlp_act!r}")
    h = constrain(h, "dp", None, "tp")
    if h.shape[-1] != wd.shape[0]:     # serve TP: concat local d_ff columns
        h = gather_tp(h, -1)
    y = h @ wd
    if y.shape[-1] != cfg.d_model:     # serve TP: concat wd columns
        y = gather_tp(y, -1)
    return constrain(y, "dp", None, None)
