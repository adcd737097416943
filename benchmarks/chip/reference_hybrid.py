"""Plain float32 reference of the served hybrid decoder (Nemotron-H), and
its fp8 control.

Imports nothing of the program under test. The weights are made again
from the run's seed by the same arithmetic as the program's
``init_params`` (a copy kept here: the key tree — one key a layer, split
by the layer's kind as its init splits it — LeCun-normal matrices,
0.2-normal conv filters, zero conv bias, ``A_log = log(1..16)``,
``dt_bias`` the inverse softplus of U(1e-3, 1e-1), 0.02-normal embedding
and head, unit norm scales), rounded to the served ``bfloat16`` and then
computed in float32 at ``highest`` matmul precision, one layer at a time,
one sequence at a time, so that a layer of 8192-wide weights fits beside
its activations.

The model is the hybrid decoder family's (``hybrid_decoder.py``), at the
sizes the configuration file states: token embedding, then per layer of
``hybrid_override_pattern`` ``x += mixer(RMSNorm(x))``, a final RMSNorm
and an untied head. The mixers:

* ``M``, Mamba-2, computed BY ITS RECURRENCE, token by token (the program
  uses the chunked SSD form): ``zxbcdt = h @ in_proj`` split into ``z``
  (d_inner), ``xBC`` (d_inner + 2 x groups x state) and ``dt`` (heads); a
  depthwise causal conv of width ``conv_kernel`` with bias over ``xBC``
  from zeros, then SiLU; ``dt = softplus(dt + dt_bias)`` unclamped
  (``time_step_limit`` [0, null]); per head ``h_t = exp(dt A) h_{t-1} +
  dt x_t B_t^T`` and ``y_t = h_t C_t + D x_t``, head ``j`` reading group
  ``j // (heads / groups)``'s B and C; ``RMSNorm(y * silu(z))`` over each
  group's d_inner / groups channels; ``out_proj``;
* ``-``, ``down(relu(up(h)) ** 2)``;
* ``*``, causal grouped-query attention with no position embedding (the
  configuration carries no rope key), softmax scale ``head_dim ** -0.5``,
  no biases, in full softmax over every earlier key (taken in blocks of
  queries, each row's softmax whole).

The control is the same computation with every linear layer taken in
fp8 (e4m3) and K/V per token and head, as ``reference.py``'s; the
recurrence stays float32. The check must reject it.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hybrid_decoder import Dims, dims_of
from reference import (HIGHEST, _best_and_served, _control_gap, _dense,
                       _embed, _fp8, _mm, _normal, _rms, embed_and_head)

__all__ = ["layer_weights", "logits", "reference_gaps"]

#: queries a block of the reference's attention (its scores, 64 heads x
#: 512 x 8192 keys in f32, stay under 1.1 GB)
Q_BLOCK = 512


# --------------------------------------------------------------- weights
def layer_weights(d: Dims, key, layer: int):
    """Layer ``layer``'s bf16 weights, keyed as the program keys them."""
    return _layer_weights(d, key, layer, d.pattern[layer])


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer_weights(d: Dims, key, layer, kind: str):
    """One compile a layer kind; ``layer`` is traced."""
    _, k_blocks, _, _ = jax.random.split(key, 4)
    kl = jax.random.split(k_blocks, len(d.pattern))[layer]
    if kind == "M":
        ks = jax.random.split(kl, 4)
        return {
            "in_proj": _dense(ks[0], (d.D, d.dI + d.C + d.nh)),
            "conv_w": _normal(ks[1], (d.C, d.K), 0.2),
            "conv_b": jnp.zeros((d.C,), jnp.bfloat16),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, d.nh)),
            "dt_bias": jnp.log(jnp.expm1(jax.random.uniform(
                ks[2], (d.nh,), minval=1e-3, maxval=1e-1))),
            "out_proj": _dense(ks[3], (d.dI, d.D))}
    if kind == "-":
        ks = jax.random.split(kl, 3)
        return {"wi": _dense(ks[0], (d.D, d.F)),
                "wd": _dense(ks[2], (d.F, d.D))}
    ks = jax.random.split(kl, 4)
    return {"wq": _dense(ks[0], (d.D, d.H * d.hd)),
            "wk": _dense(ks[1], (d.D, d.KV * d.hd)),
            "wv": _dense(ks[2], (d.D, d.KV * d.hd)),
            "wo": _dense(ks[3], (d.H * d.hd, d.D))}


# --------------------------------------------------------------- mixers
def _mamba(d: Dims, w, h, low: bool):
    """Mamba-2 over one sequence ``h`` (T, D), token by token."""
    T = h.shape[0]
    Hg = d.nh // d.G
    zxbcdt = _mm(h, w["in_proj"], low)
    z, xbc, dt = jnp.split(zxbcdt, [d.dI, d.dI + d.C], axis=-1)
    cw = w["conv_w"].astype(jnp.float32)
    xp = jnp.concatenate([jnp.zeros((d.K - 1, d.C), jnp.float32), xbc])
    conv = sum(xp[k:k + T] * cw[:, k] for k in range(d.K))
    xbc = jax.nn.silu(conv + w["conv_b"].astype(jnp.float32))
    x, B, C = jnp.split(xbc, [d.dI, d.dI + d.G * d.N], axis=-1)
    dt = jax.nn.softplus(dt + w["dt_bias"])                  # (T, nh)
    A = -jnp.exp(w["A_log"])                                 # (nh,)
    x = x.reshape(T, d.G, Hg, d.hp)
    B = B.reshape(T, d.G, d.N)
    C = C.reshape(T, d.G, d.N)
    dt = dt.reshape(T, d.G, Hg)
    A = A.reshape(d.G, Hg)

    def step(s, inp):
        x_t, B_t, C_t, dt_t = inp
        s = jnp.exp(dt_t * A)[..., None, None] * s \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :]
        y = jnp.einsum("ghpn,gn->ghp", s, C_t, precision=HIGHEST)
        return s, y

    s0 = jnp.zeros((d.G, Hg, d.hp, d.N), jnp.float32)
    _, y = jax.lax.scan(step, s0, (x, B, C, dt))
    y = (y + x).reshape(T, d.dI)                 # D = 1: the skip
    g = (y * jax.nn.silu(z)).reshape(T, d.G, d.dI // d.G)
    g = _rms(g, d.eps).reshape(T, d.dI)          # unit norm scale
    return _mm(g, w["out_proj"], low)


def _mlp(d: Dims, w, h, low: bool):
    return _mm(jnp.square(jax.nn.relu(_mm(h, w["wi"], low))), w["wd"], low)


def _attention(d: Dims, w, h, low: bool):
    """Causal GQA over one sequence, no position embedding."""
    T = h.shape[0]
    G = d.H // d.KV
    q = _mm(h, w["wq"], low).reshape(T, d.KV, G, d.hd)
    k = _mm(h, w["wk"], low).reshape(T, d.KV, d.hd)
    v = _mm(h, w["wv"], low).reshape(T, d.KV, d.hd)
    if low:
        k, v = _fp8(k, -1), _fp8(v, -1)
    Q = Q_BLOCK if T % Q_BLOCK == 0 else T

    def block(args):
        qb, pos = args                              # (Q, KV, G, hd), (Q,)
        s = jnp.einsum("qkgh,skh->kgqs", qb, k,
                       precision=HIGHEST) * d.hd ** -0.5
        s = jnp.where(pos[:, None] >= jnp.arange(T)[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqs,skh->qkgh", p, v, precision=HIGHEST)

    a = jax.lax.map(block, (q.reshape(T // Q, Q, d.KV, G, d.hd),
                            jnp.arange(T).reshape(T // Q, Q)))
    return _mm(a.reshape(T, d.H * d.hd), w["wo"], low)


MIXERS = {"M": _mamba, "-": _mlp, "*": _attention}


@functools.partial(jax.jit, static_argnums=(0, 1, 4), donate_argnums=(3,))
def _layer_all(d: Dims, kind: str, w, xs, low: bool):
    """One layer over every sequence of the padded batch, one at a time
    (causal mixers never look past a sequence's own end)."""
    mix = MIXERS[kind]
    return jax.lax.map(lambda x: x + mix(d, w, _rms(x, d.eps), low), xs)


def _forward(d: Dims, key, x, low: bool):
    for layer, kind in enumerate(d.pattern):
        x = _layer_all(d, kind, layer_weights(d, key, layer), x, low)
    return x


def logits(model: Dict, seed: int, tokens: np.ndarray) -> np.ndarray:
    """The reference's float32 logits ``(n, T, vocab)`` over every position
    of ``tokens`` ``(n, T)``: the whole forward pass, for small sizes."""
    d = dims_of(model)
    key = jax.random.PRNGKey(seed)
    with jax.default_matmul_precision("highest"):
        embed, head = embed_and_head(d, key)
        x = _forward(d, key, _embed(d, embed, jnp.asarray(tokens, jnp.int32)),
                     False)
        out = _mm(_rms(x, d.eps), head, False)[..., :d.V]
    return np.asarray(out)


def reference_gaps(model: Dict, seed: int,
                   requests: Sequence[Tuple[np.ndarray, np.ndarray]],
                   control: bool = False, pad_to: int = Q_BLOCK
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
        x_low = _forward(d, key, x + 0.0, True) if control else None
        x = _forward(d, key, x, False)
        lr, best, gap = _best_and_served(d, head, x, pos, served, live)
        low = _control_gap(d, head, x_low, pos, lr, best, live) \
            if control else None
    return np.asarray(gap), None if low is None else np.asarray(low)
