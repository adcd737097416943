"""Plain float32 reference of the served decoder, and its fp8 control.

Imports nothing of the program under test. The weights are made again
from the run's seed by the same arithmetic as the program's
``init_params`` (a copy kept here: the key tree, LeCun-normal matrices,
0.02-normal embedding and head, unit norm scales), rounded to the served
``bfloat16`` and then computed in float32 at ``highest`` matmul precision,
layer by layer, so that a 12B-wide layer stack fits beside its
activations.

The decoder is the dense decoder family's (``dense_decoder.py``), at the
sizes the configuration file states: token embedding, then per layer ``x
+= Attn(RMSNorm(x))`` and ``x += SwiGLU(RMSNorm(x))``, a final RMSNorm and
an untied head. Attention is causal grouped-query attention with rotary
embedding over the whole head (the two halves of each head rotated as a
pair), softmax scale ``head_dim ** -0.5``, no biases. For stablelm-1.6b
these depart from the published StableLM-2 (LayerNorm with bias, rotary
over a quarter of each head, biases on q/k/v): the file lists them under
``departures``, and this reference follows the configuration as the
program runs it.

The control is the same computation with every linear layer taken in
fp8 (e4m3): weights scaled per output column, inputs per row, and K/V
per token and head, each to the format's largest value. It is what a
lower-precision serving path would compute, and the check must reject
it.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dense_decoder import Dims, dims_of

__all__ = ["embed_and_head", "layer_weights", "logits", "reference_gaps"]

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------- weights
def _normal(key, shape, scale):
    return (jax.random.normal(key, shape) * scale).astype(jnp.bfloat16)


def _dense(key, shape):
    return _normal(key, shape, 1.0 / max(1.0, shape[0]) ** 0.5)


@functools.partial(jax.jit, static_argnums=(0,))
def embed_and_head(d: Dims, key):
    """The bf16 embedding ``(Vp, D)`` and head ``(D, Vp)``."""
    k_embed, _, k_head, _ = jax.random.split(key, 4)
    return (_normal(k_embed, (d.Vp, d.D), 0.02),
            _normal(k_head, (d.D, d.Vp), 0.02))


@functools.partial(jax.jit, static_argnums=(0,))
def layer_weights(d: Dims, key, layer):
    """Layer ``layer``'s bf16 matrices, keyed as the program keys them."""
    _, k_blocks, _, _ = jax.random.split(key, 4)
    kl = jax.random.split(k_blocks, d.L)[layer]
    ks = jax.random.split(kl, 3)
    ka = jax.random.split(ks[0], 4)
    km = jax.random.split(ks[1], 3)
    return {"wq": _dense(ka[0], (d.D, d.H * d.hd)),
            "wk": _dense(ka[1], (d.D, d.KV * d.hd)),
            "wv": _dense(ka[2], (d.D, d.KV * d.hd)),
            "wo": _dense(ka[3], (d.H * d.hd, d.D)),
            "wi": _dense(km[0], (d.D, d.F)),
            "wg": _dense(km[1], (d.D, d.F)),
            "wd": _dense(km[2], (d.F, d.D))}


# --------------------------------------------------------------- forward
def _fp8(x, axis):
    """Round ``x`` through e4m3 with a scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, low: bool):
    """``x @ w`` in float32, or with both sides rounded through fp8."""
    w = w.astype(jnp.float32)
    if low:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope_tables(d: Dims, T: int):
    inv = 1.0 / (d.theta ** (np.arange(0, d.hd, 2, dtype=np.float64) / d.hd))
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang), jnp.float32)[:, None, :],
            jnp.asarray(np.sin(ang), jnp.float32)[:, None, :])


def _rope(x, cos, sin):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(d: Dims, w, x, cos, sin, low: bool):
    """One decoder layer over one sequence ``x`` of shape ``(T, D)``."""
    T = x.shape[0]
    G = d.H // d.KV
    h = _rms(x, d.eps)
    q = _mm(h, w["wq"], low).reshape(T, d.KV, G, d.hd)
    k = _mm(h, w["wk"], low).reshape(T, d.KV, d.hd)
    v = _mm(h, w["wv"], low).reshape(T, d.KV, d.hd)
    q = _rope(q, cos[:, :, None], sin[:, :, None])
    k = _rope(k, cos, sin)
    if low:
        k, v = _fp8(k, -1), _fp8(v, -1)
    s = jnp.einsum("qkgh,skh->kgqs", q, k, precision=HIGHEST) * d.hd ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("kgqs,skh->qkgh", p, v,
                   precision=HIGHEST).reshape(T, d.H * d.hd)
    x = x + _mm(a, w["wo"], low)
    h = _rms(x, d.eps)
    f = jax.nn.silu(_mm(h, w["wg"], low)) * _mm(h, w["wi"], low)
    return x + _mm(f, w["wd"], low)


@functools.partial(jax.jit, static_argnums=(0, 3), donate_argnums=(2,))
def _layer_all(d: Dims, w, xs, low: bool):
    """One layer over every sequence of the padded batch, one at a time
    (causal attention never looks past a sequence's own end)."""
    cos, sin = _rope_tables(d, xs.shape[1])
    return jax.lax.map(lambda x: _layer(d, w, x, cos, sin, low), xs)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(d: Dims, embed, tokens):
    return jnp.take(embed, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0,))
def _best_and_served(d: Dims, head, x, pos, served, live):
    """Per position: the reference's logits, its best, and the gap by
    which the served token's logit lies below that best."""
    h = _rms(jax.vmap(lambda a, p: a[p])(x, pos), d.eps)
    lr = _mm(h, head, False)[..., :d.V]
    best = jnp.max(lr, axis=-1)
    got = jnp.take_along_axis(lr, served[..., None], axis=-1)[..., 0]
    return lr, best, jnp.where(live, best - got, 0.0)


@functools.partial(jax.jit, static_argnums=(0,))
def _control_gap(d: Dims, head, x_low, pos, lr, best, live):
    """Per position: the gap of the fp8 control's first choice below the
    reference's best."""
    h = _rms(jax.vmap(lambda a, p: a[p])(x_low, pos), d.eps)
    top = jnp.argmax(_mm(h, head, True)[..., :d.V], axis=-1)
    got = jnp.take_along_axis(lr, top[..., None], axis=-1)[..., 0]
    return jnp.where(live, best - got, 0.0)


def logits(model: Dict, seed: int, tokens: np.ndarray) -> np.ndarray:
    """The reference's float32 logits ``(n, T, vocab)`` over every position
    of ``tokens`` ``(n, T)``: the whole forward pass, for small sizes."""
    d = dims_of(model)
    key = jax.random.PRNGKey(seed)
    with jax.default_matmul_precision("highest"):
        embed, head = embed_and_head(d, key)
        x = _embed(d, embed, jnp.asarray(tokens, jnp.int32))
        for layer in range(d.L):
            x = _layer_all(d, layer_weights(d, key, layer), x, False)
        out = _mm(_rms(x, d.eps), head, False)[..., :d.V]
    return np.asarray(out)


def reference_gaps(model: Dict, seed: int,
                   requests: Sequence[Tuple[np.ndarray, np.ndarray]],
                   control: bool = False, pad_to: int = 256
                   ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """For each ``(prompt, served)`` pair, the gap by which each served
    token's float32-reference logit lies below the reference's best at its
    position, and with ``control`` the same for the fp8 control's own
    first choice there. Returns ``(n_requests, max_served)`` arrays, 0
    past each request's served tokens (the second None without
    ``control``)."""
    d = dims_of(model)
    key = jax.random.PRNGKey(seed)
    n = len(requests)
    seqs = [np.concatenate([p, s[:-1]]).astype(np.int32) for p, s in requests]
    T = -(-max(len(s) for s in seqs) // pad_to) * pad_to
    S = max(len(s) for _, s in requests)
    tokens = np.zeros((n, T), np.int32)
    pos = np.zeros((n, S), np.int32)
    served = np.zeros((n, S), np.int32)
    live = np.zeros((n, S), bool)
    for i, ((p, s), q) in enumerate(zip(requests, seqs)):
        tokens[i, :len(q)] = q
        pos[i, :len(s)] = len(p) - 1 + np.arange(len(s))
        served[i, :len(s)] = s
        live[i, :len(s)] = True
    pos, served, live = map(jnp.asarray, (pos, served, live))
    with jax.default_matmul_precision("highest"):
        embed, head = embed_and_head(d, key)
        x = _embed(d, embed, jnp.asarray(tokens))
        del embed
        x_low = x + 0.0 if control else None
        for layer in range(d.L):
            w = layer_weights(d, key, layer)
            x = _layer_all(d, w, x, False)
            if control:
                x_low = _layer_all(d, w, x_low, True)
            del w
        lr, best, gap = _best_and_served(d, head, x, pos, served, live)
        low = _control_gap(d, head, x_low, pos, lr, best, live) \
            if control else None
    return np.asarray(gap), None if low is None else np.asarray(low)
