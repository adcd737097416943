"""Mamba1 (selective scan) and Mamba2 (SSD) blocks.

Mamba1 (falcon-mamba): chunked selective scan — within a chunk the linear
recurrence h_t = a_t*h_{t-1} + b_t runs as a log-depth associative scan;
chunks are linked by a lax.scan carry. Activation memory is O(S_chunk * dI * N)
instead of O(S * dI * N).

Mamba2 (zamba2; nemotron-h, with 8 B/C groups): the **SSD dual form** —
scalar-per-head decay turns the recurrence into (i) a causal matmul within
each chunk (MXU-friendly) and (ii) a tiny cross-chunk state recurrence. This
is the TPU-native adaptation: the GPU implementation's fused scan kernel
becomes matmuls + one short scan.

Both blocks expose ``*_step`` single-token decode paths carrying
(conv_buffer, ssm_state) — this is what makes 500k-token decoding O(1) per
step (no KV cache), the reason long_500k is assigned to these archs.
:func:`mamba_window` runs Mamba-2 over one chunked-prefill window from a
carried (conv window, h), so a prompt prefills across windows.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import constrain
from .layers import dense_init, dtype_of, normal_init, rms_norm

__all__ = ["init_mamba", "mamba_forward", "mamba_window", "mamba_step",
           "init_mamba_state"]


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                 prefix=None) -> jnp.ndarray:
    """Depthwise causal conv. x: (B,S,C); w: (C,K); b: (C,). ``prefix``
    (B,K-1,C): the K-1 inputs before x (a carried conv window); zeros
    when None."""
    B, S, C = x.shape
    K = w.shape[1]
    if prefix is None:
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([prefix.astype(x.dtype), x], axis=1)
    y = sum(xp[:, k:k + S, :] * w[:, k] for k in range(K))
    return y + b


def _conv_step(buf: jnp.ndarray, x1: jnp.ndarray, w: jnp.ndarray,
               b: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-token conv. buf: (B,K-1,C) past inputs; x1: (B,C)."""
    window = jnp.concatenate([buf, x1[:, None, :]], axis=1)   # (B,K,C)
    y = jnp.einsum("bkc,ck->bc", window, w) + b
    return y, window[:, 1:, :]


# ===================================================================== Mamba1
def _init_m1(key, cfg: ModelConfig) -> Dict[str, jnp.ndarray]:
    D, dI, N, R, K = (cfg.d_model, cfg.d_inner, cfg.ssm_state,
                      cfg.dt_rank_, cfg.ssm_conv)
    pdt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    A = jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32)[None, :], (dI, 1))
    return {
        "in_proj": dense_init(ks[0], (D, 2 * dI), pdt),
        "conv_w": normal_init(ks[1], (dI, K), 0.2, pdt),
        "conv_b": jnp.zeros((dI,), pdt),
        "x_proj": dense_init(ks[2], (dI, R + 2 * N), pdt),
        "dt_proj": normal_init(ks[3], (R, dI), R ** -0.5, pdt),
        "dt_bias": jnp.log(jnp.expm1(  # softplus^-1 of U(1e-3, 1e-1)
            jax.random.uniform(ks[4], (dI,), minval=1e-3, maxval=1e-1)
        )).astype(pdt),
        "A_log": jnp.log(A),                      # fp32: recurrence stability
        "ssm_D": jnp.ones((dI,), jnp.float32),
        "out_proj": dense_init(ks[5], (dI, D), pdt),
    }


def _m1_scan(dt, A, Bc, Cc, xh, h0, chunk: int, constrain_tp: bool = False):
    """Chunked selective scan. dt,xh: (B,S,dI); A: (dI,N); Bc,Cc: (B,S,N);
    h0: (B,dI,N) fp32. Returns y (B,S,dI) and final state."""
    B_, S, dI = xh.shape
    N = A.shape[1]
    chunk = min(chunk, S)
    if S % chunk:
        chunk = S  # smoke shapes: fall back to one chunk
    nch = S // chunk

    def to_chunks(t):
        return t.reshape(B_, nch, chunk, *t.shape[2:]).swapaxes(0, 1)

    def chunk_step(h, inp):
        dt_c, x_c, b_c, c_c = inp
        if constrain_tp:
            # §Perf H4: keep the channel dim sharded through the chunk
            # body — GSPMD otherwise replicates the (B,c,dI,N) tensors
            dt_c = constrain(dt_c, "dp", None, "tp")
            x_c = constrain(x_c, "dp", None, "tp")
        a = jnp.exp(dt_c[..., None] * A)                       # (B,c,dI,N)
        b = (dt_c * x_c)[..., None] * b_c[:, :, None, :]       # (B,c,dI,N)
        if constrain_tp:
            a = constrain(a, "dp", None, "tp", None)
            b = constrain(b, "dp", None, "tp", None)

        def comb(l, r):
            al, bl = l
            ar, br = r
            return al * ar, ar * bl + br

        aa, bb = jax.lax.associative_scan(comb, (a, b), axis=1)
        hs = aa * h[:, None] + bb                              # (B,c,dI,N)
        if constrain_tp:
            hs = constrain(hs, "dp", None, "tp", None)
        y_c = jnp.einsum("bcdn,bcn->bcd", hs, c_c)
        return hs[:, -1], y_c

    hT, ys = jax.lax.scan(
        chunk_step, h0,
        (to_chunks(dt.astype(jnp.float32)), to_chunks(xh.astype(jnp.float32)),
         to_chunks(Bc.astype(jnp.float32)), to_chunks(Cc.astype(jnp.float32))))
    y = ys.swapaxes(0, 1).reshape(B_, S, dI)
    return y, hT


def _m1_forward(p, x, cfg: ModelConfig, h0=None, return_state=False):
    B, S, D = x.shape
    dI, N, R = cfg.d_inner, cfg.ssm_state, cfg.dt_rank_
    cdt = dtype_of(cfg.compute_dtype)
    xz = x @ p["in_proj"].astype(cdt)
    xh, z = jnp.split(xz, 2, axis=-1)
    xh = constrain(xh, "dp", None, "tp")
    xh = jax.nn.silu(_causal_conv(xh, p["conv_w"].astype(cdt),
                                  p["conv_b"].astype(cdt)))
    proj = xh @ p["x_proj"].astype(cdt)
    dtr, Bc, Cc = jnp.split(proj, [R, R + N], axis=-1)
    dt = jax.nn.softplus(
        dtr @ p["dt_proj"].astype(cdt)
        + p["dt_bias"].astype(jnp.float32))                    # (B,S,dI) f32
    dt = constrain(dt, "dp", None, "tp")
    A = -jnp.exp(p["A_log"])                                   # (dI,N) f32
    if h0 is None:
        h0 = jnp.zeros((B, dI, N), jnp.float32)
    y, hT = _m1_scan(dt, A, Bc, Cc, xh, h0, cfg.ssm_chunk,
                     constrain_tp=cfg.ssm_scan_constrain)
    y = y + p["ssm_D"] * xh.astype(jnp.float32)
    y = (y.astype(cdt)) * jax.nn.silu(z)
    out = y @ p["out_proj"].astype(cdt)
    out = constrain(out, "dp", None, None)
    if return_state:
        # conv tail: last K-1 pre-conv inputs (recompute projection tail)
        tail = (x[:, -(cfg.ssm_conv - 1):, :]
                @ p["in_proj"].astype(cdt))[..., :dI]
        return out, (tail, hT)
    return out


def _m1_step(p, x1, cfg: ModelConfig, state):
    """x1: (B, D); state = (conv_buf (B,K-1,dI), h (B,dI,N))."""
    conv_buf, h = state
    cdt = dtype_of(cfg.compute_dtype)
    dI, N, R = cfg.d_inner, cfg.ssm_state, cfg.dt_rank_
    xz = x1 @ p["in_proj"].astype(cdt)
    xh, z = jnp.split(xz, 2, axis=-1)
    xh, conv_buf = _conv_step(conv_buf.astype(cdt), xh,
                              p["conv_w"].astype(cdt),
                              p["conv_b"].astype(cdt))
    xh = jax.nn.silu(xh)
    proj = xh @ p["x_proj"].astype(cdt)
    dtr, Bc, Cc = jnp.split(proj, [R, R + N], axis=-1)
    dt = jax.nn.softplus(dtr @ p["dt_proj"].astype(cdt)
                         + p["dt_bias"].astype(jnp.float32))   # (B,dI)
    A = -jnp.exp(p["A_log"])
    a = jnp.exp(dt[..., None] * A)                             # (B,dI,N)
    h = a * h + (dt * xh.astype(jnp.float32))[..., None] \
        * Bc.astype(jnp.float32)[:, None, :]
    y = jnp.einsum("bdn,bn->bd", h, Cc.astype(jnp.float32)) \
        + p["ssm_D"] * xh.astype(jnp.float32)
    y = y.astype(cdt) * jax.nn.silu(z)
    return y @ p["out_proj"].astype(cdt), (conv_buf, h)


# ===================================================================== Mamba2
def _init_m2(key, cfg: ModelConfig) -> Dict[str, jnp.ndarray]:
    D, dI, K = cfg.d_model, cfg.d_inner, cfg.ssm_conv
    nh = cfg.ssm_heads
    pdt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    conv_dim = cfg.ssm_conv_dim
    return {
        "in_proj": dense_init(ks[0], (D, dI + conv_dim + nh), pdt),
        "conv_w": normal_init(ks[1], (conv_dim, K), 0.2, pdt),
        "conv_b": jnp.zeros((conv_dim,), pdt),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh)).astype(jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(
            jax.random.uniform(ks[2], (nh,), minval=1e-3, maxval=1e-1)
        )).astype(jnp.float32),
        "ssm_D": jnp.ones((nh,), jnp.float32),
        "ssm_norm": jnp.ones((dI,), pdt),
        "out_proj": dense_init(ks[3], (dI, D), pdt),
    }


def _ssd_scan(xh, dt, A, Bc, Cc, h0, chunk: int):
    """SSD dual form over B/C groups. xh: (B,S,G,Hg,hp); dt: (B,S,G,Hg)
    f32; A: (G,Hg) f32; Bc,Cc: (B,S,G,N); h0: (B,G,Hg,hp,N) f32 — head
    ``g * Hg + j`` reads group g's B and C. Returns y (B,S,G,Hg,hp) and the
    final h."""
    B_, S = xh.shape[:2]
    chunk = min(chunk, S)
    if S % chunk:
        chunk = S
    nch = S // chunk

    def to_chunks(t):
        return t.reshape(B_, nch, chunk, *t.shape[2:]).swapaxes(0, 1)

    loga = dt * A                                              # <= 0

    def chunk_step(h, inp):
        # (B,c,G,Hg,hp) (B,c,G,Hg) (B,c,G,Hg) (B,c,G,N) (B,c,G,N)
        x_c, dt_c, la_c, b_c, c_c = inp
        L = jnp.cumsum(la_c, axis=1)                           # (B,c,G,Hg)
        # intra-chunk: causal "attention" with decay
        seg = L[:, :, None] - L[:, None]                       # (B,t,s,G,Hg)
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.where(tri[None, :, :, None, None], jnp.exp(seg), 0.0)
        cb = jnp.einsum("btgn,bsgn->btsg", c_c, b_c)           # (B,t,s,G)
        w = cb[..., None] * decay * dt_c[:, None]              # (B,t,s,G,Hg)
        y = jnp.einsum("btsgh,bsghp->btghp", w, x_c)
        # inter-chunk: contribution of carried state
        y = y + jnp.einsum("btgn,bghpn->btghp", c_c, h) \
            * jnp.exp(L)[..., None]
        # chunk state: sum_s exp(L_last - L_s) dt_s x_s B_s^T
        rdecay = jnp.exp(L[:, -1:] - L)                        # (B,c,G,Hg)
        hc = jnp.einsum("bsghp,bsgn->bghpn",
                        x_c * (dt_c * rdecay)[..., None], b_c)
        h = h * jnp.exp(L[:, -1])[..., None, None] + hc
        return h, y

    hT, ys = jax.lax.scan(
        chunk_step, h0,
        (to_chunks(xh.astype(jnp.float32)), to_chunks(dt),
         to_chunks(loga), to_chunks(Bc.astype(jnp.float32)),
         to_chunks(Cc.astype(jnp.float32))))
    y = ys.swapaxes(0, 1).reshape(xh.shape)
    return y, hT


def _m2_split(p, x, cfg: ModelConfig):
    """In-projection of Mamba-2: (z, xBC before the conv, dt before its
    softplus)."""
    dI, C = cfg.d_inner, cfg.ssm_conv_dim
    cdt = dtype_of(cfg.compute_dtype)
    zxbcdt = x @ p["in_proj"].astype(cdt)
    return jnp.split(zxbcdt, [dI, dI + C], axis=-1)


def _gated_norm(p, y, z, cfg: ModelConfig):
    """RMSNorm of ``y * silu(z)`` over each B/C group's d_inner / groups
    channels (all of d_inner with one group)."""
    g = y * jax.nn.silu(z)
    G = cfg.ssm_groups
    if G == 1:
        return rms_norm(g, p["ssm_norm"], cfg.rms_eps)
    shp = g.shape
    g = g.reshape(shp[:-1] + (G, shp[-1] // G))
    return rms_norm(g, p["ssm_norm"].reshape(G, -1), cfg.rms_eps) \
        .reshape(shp)


def _m2_mix(p, z, xbc, dt, cfg: ModelConfig, h0):
    """Mamba-2 after the conv: SSD scan from ``h0``, the D skip, the gated
    norm and the out-projection. xbc: (B,S,conv_dim) post-conv; dt:
    (B,S,nh) f32 post-softplus. Returns (out, hT)."""
    B, S = xbc.shape[:2]
    dI, N, nh, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    G = cfg.ssm_groups
    Hg = nh // G
    cdt = dtype_of(cfg.compute_dtype)
    xh, Bc, Cc = jnp.split(xbc, [dI, dI + G * N], axis=-1)
    A = -jnp.exp(p["A_log"])                                     # (nh,)
    xh = constrain(xh, "dp", None, "tp")
    xhh = xh.reshape(B, S, G, Hg, hp)
    if h0 is None:
        h0 = jnp.zeros((B, nh, hp, N), jnp.float32)
    y, hT = _ssd_scan(xhh, dt.reshape(B, S, G, Hg), A.reshape(G, Hg),
                      Bc.reshape(B, S, G, N), Cc.reshape(B, S, G, N),
                      h0.reshape(B, G, Hg, hp, N), cfg.ssm_chunk)
    y = y + p["ssm_D"].reshape(G, Hg)[:, :, None] * xhh.astype(jnp.float32)
    y = _gated_norm(p, y.reshape(B, S, dI).astype(cdt), z, cfg)
    out = y @ p["out_proj"].astype(cdt)
    return constrain(out, "dp", None, None), hT.reshape(B, nh, hp, N)


def _m2_forward(p, x, cfg: ModelConfig, h0=None, return_state=False):
    dI = cfg.d_inner
    cdt = dtype_of(cfg.compute_dtype)
    z, xbc, dt = _m2_split(p, x, cfg)
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"].astype(cdt),
                                   p["conv_b"].astype(cdt)))
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,S,nh)
    out, hT = _m2_mix(p, z, xbc, dt, cfg, h0)
    if return_state:
        tail = (x[:, -(cfg.ssm_conv - 1):, :] @ p["in_proj"].astype(cdt)
                )[..., dI:dI + cfg.ssm_conv_dim]
        return out, (tail, hT)
    return out


def mamba_window(p, x, cfg: ModelConfig, state, valid):
    """Mamba-2 over a prefill WINDOW that continues a carried state.

    x: (B,S,D); state = (conv (B,K-1,conv_dim) — the K-1 inputs of the conv
    before the window, h (B,nh,hp,N) f32); valid: (B,S) bool, a per-row
    prefix (the rest of the window is padding). The conv reads the carried
    window instead of zeros, padded positions take ``dt = 0`` (no decay, no
    input: the state passes them unchanged), and the returned conv window
    ends at each row's last valid token. A row with no valid token keeps
    its state exactly. Returns (out (B,S,D), (conv, h))."""
    conv0, h0 = state
    K = cfg.ssm_conv
    cdt = dtype_of(cfg.compute_dtype)
    z, xbc, dt = _m2_split(p, x, cfg)
    prefix = conv0.astype(xbc.dtype)
    xc = jax.nn.silu(_causal_conv(xbc, p["conv_w"].astype(cdt),
                                  p["conv_b"].astype(cdt), prefix))
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    dt = jnp.where(valid[..., None], dt, 0.0)
    out, hT = _m2_mix(p, z, xc, dt, cfg, h0)
    n = jnp.sum(valid, axis=1).astype(jnp.int32)                 # (B,)
    seq = jnp.concatenate([prefix, xbc], axis=1)              # (B,K-1+S,C)
    idx = n[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None]
    conv = jnp.take_along_axis(seq, idx[..., None], axis=1)
    live = n > 0
    hT = jnp.where(live[:, None, None, None], hT, h0)
    return out, (conv.astype(conv0.dtype), hT)


def _m2_step(p, x1, cfg: ModelConfig, state):
    conv_buf, h = state
    B = x1.shape[0]
    dI, N, nh, hp = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    G = cfg.ssm_groups
    Hg = nh // G
    cdt = dtype_of(cfg.compute_dtype)
    z, xbc, dt = _m2_split(p, x1, cfg)
    xbc, conv_buf = _conv_step(conv_buf.astype(cdt), xbc,
                               p["conv_w"].astype(cdt),
                               p["conv_b"].astype(cdt))
    xbc = jax.nn.silu(xbc)
    xh, Bc, Cc = jnp.split(xbc, [dI, dI + G * N], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])  # (B,nh)
    A = -jnp.exp(p["A_log"])
    a = jnp.exp(dt * A).reshape(B, G, Hg)
    xhh = xh.reshape(B, G, Hg, hp).astype(jnp.float32)
    Bg = Bc.astype(jnp.float32).reshape(B, G, 1, 1, N)
    Cg = Cc.astype(jnp.float32).reshape(B, G, N)
    hg = a[..., None, None] * h.reshape(B, G, Hg, hp, N) \
        + (dt.reshape(B, G, Hg)[..., None] * xhh)[..., None] * Bg
    y = jnp.einsum("bghpn,bgn->bghp", hg, Cg) \
        + p["ssm_D"].reshape(G, Hg)[:, :, None] * xhh
    y = _gated_norm(p, y.reshape(B, dI).astype(cdt), z, cfg)
    return y @ p["out_proj"].astype(cdt), (conv_buf, hg.reshape(h.shape))


# ==================================================================== dispatch
def init_mamba(key, cfg: ModelConfig) -> Dict[str, jnp.ndarray]:
    return _init_m1(key, cfg) if cfg.ssm_version == 1 else _init_m2(key, cfg)


def mamba_forward(p, x, cfg: ModelConfig, h0=None, return_state=False):
    f = _m1_forward if cfg.ssm_version == 1 else _m2_forward
    return f(p, x, cfg, h0, return_state)


def mamba_step(p, x1, cfg: ModelConfig, state):
    f = _m1_step if cfg.ssm_version == 1 else _m2_step
    return f(p, x1, cfg, state)


def init_mamba_state(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    """(conv_buf, h) zeros for decode."""
    K = cfg.ssm_conv
    if cfg.ssm_version == 1:
        return (jnp.zeros((batch, K - 1, cfg.d_inner), dtype),
                jnp.zeros((batch, cfg.d_inner, cfg.ssm_state), jnp.float32))
    return (jnp.zeros((batch, K - 1, cfg.ssm_conv_dim), dtype),
            jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_head_dim,
                       cfg.ssm_state), jnp.float32))
