"""GQA attention: chunked-causal (train/prefill) + cached decode.

Three implementations share one math definition (``ref`` oracle lives in
``repro/kernels/ref.py``):

* ``full``    — materializes (S x S) scores; short sequences / smoke tests.
* ``chunked`` — lax.scan over query blocks with a causal mask; O(S * chunk)
                activation memory. This is the XLA-level flash pattern and
                the default for the dry-run meshes.
* ``pallas``  — the TPU flash kernel in ``repro/kernels/flash_attention.py``
                (validated in interpret mode; selected via cfg when on TPU).

Sharding: weights follow Megatron column/row specs (sharding.py); activation
constraints keep (B,S,·) on the dp axis and let GSPMD propagate the head
dimension from the weight shards.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import constrain, gather_tp
from .layers import dense_init, dtype_of, rms_norm, rope

__all__ = ["init_attention", "attention", "decode_attention",
           "decode_attention_rows", "paged_decode_attention",
           "paged_prefill_window_attention", "NEG_INF"]

NEG_INF = -2.0 ** 30  # large-but-finite: keeps bf16 softmax NaN-free


def init_attention(key, cfg: ModelConfig) -> Dict[str, jnp.ndarray]:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.hd
    pdt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (D, H * hd), pdt),
        "wk": dense_init(ks[1], (D, KV * hd), pdt),
        "wv": dense_init(ks[2], (D, KV * hd), pdt),
        "wo": dense_init(ks[3], (H * hd, D), pdt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), pdt)
        p["bk"] = jnp.zeros((KV * hd,), pdt)
        p["bv"] = jnp.zeros((KV * hd,), pdt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), pdt)
        p["k_norm"] = jnp.ones((hd,), pdt)
    return p


def _project_qkv(p, x, cfg: ModelConfig, positions):
    B, S, D = x.shape
    # head counts come from the WEIGHTS, not cfg: under shard_map serve TP
    # each shard sees its local KV/mp and H/mp head slices
    hd = cfg.hd
    H = p["wq"].shape[-1] // hd
    KV = p["wk"].shape[-1] // hd
    cdt = dtype_of(cfg.compute_dtype)
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,dh->bsh", x, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,dh->bsh", x, p["wv"].astype(cdt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(cdt)
        k = k + p["bk"].astype(cdt)
        v = v + p["bv"].astype(cdt)
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _full_attention(q, k, v, q_pos, k_pos):
    """Reference path: (B,S,H,hd) x (B,T,KV,hd) with causal mask."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                   preferred_element_type=jnp.float32)
    s = s * (hd ** -0.5)
    mask = q_pos[:, None, None, :, None] >= k_pos[:, None, None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    pmax = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - pmax)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v)
    return out.reshape(B, S, H, hd)


def _chunked_attention(q, k, v, q_pos, k_pos, chunk_q: int,
                       bwd_remat: bool = False):
    """lax.scan over query chunks; keys stay whole (masked). Activation
    memory O(S*chunk) instead of O(S^2).

    bwd_remat=True is the flash-backward pattern: scores/probs are
    RECOMPUTED per chunk in the backward pass instead of being stacked
    across the scan (saves O(S^2) fp32 HBM traffic per layer —
    EXPERIMENTS.md §Perf H1)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    pad = (-S) % chunk_q
    if pad:
        # ragged tail (§Perf H5): pad the QUERY side only — padded rows are
        # fully masked (q_pos = -inf) and sliced off; keys stay whole. The
        # earlier fallback to full attention materialized O(S^2) scores
        # whenever frontend tokens made S_total non-divisible (musicgen).
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad)),
                        constant_values=-(2 ** 30))
    Sp = S + pad
    nq = Sp // chunk_q
    qg = q.reshape(B, nq, chunk_q, KV, G, hd).transpose(1, 0, 2, 3, 4, 5)
    qp = q_pos.reshape(B, nq, chunk_q).transpose(1, 0, 2)

    def body(_, inp):
        qb, qpb = inp                                   # (B,cq,KV,G,hd)
        s = jnp.einsum("bqkgh,bskh->bkgqs", qb, k,
                       preferred_element_type=jnp.float32) * (hd ** -0.5)
        mask = qpb[:, None, None, :, None] >= k_pos[:, None, None, None, :]
        s = jnp.where(mask, s, NEG_INF)
        pmax = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - pmax)
        probs = e / jnp.sum(e, axis=-1, keepdims=True)
        ob = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v)
        return None, ob.reshape(B, chunk_q, H, hd)

    if bwd_remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    _, out = jax.lax.scan(body, None, (qg, qp))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, Sp, H, hd)
    return out[:, :S]


def attention(p, x, cfg: ModelConfig, positions,
              impl: str = "chunked",
              return_kv: bool = False):
    """Causal self-attention over the whole sequence (train / prefill)."""
    B, S, D = x.shape
    cdt = dtype_of(cfg.compute_dtype)
    q, k, v = _project_qkv(p, x, cfg, positions)
    q = constrain(q, "dp", None, "tp", None)
    k = constrain(k, "dp", None, None, None)
    v = constrain(v, "dp", None, None, None)
    if impl == "pallas":
        from ..kernels.ops import flash_attention
        out = flash_attention(q, k, v, causal=True)
    elif impl == "full" or S <= cfg.attn_chunk_q or \
            (S % cfg.attn_chunk_q != 0 and S <= 8192):
        # ragged mid-length sequences: measured BETTER with one fused
        # S^2 attention than with padded chunking (musicgen train_4k:
        # frac 0.0243 full vs 0.0215 chunked — EXPERIMENTS.md §Perf H5);
        # long ragged sequences must chunk (O(S^2) fp32 would be ~8GB+)
        out = _full_attention(q, k, v, positions, positions)
    else:
        out = _chunked_attention(q, k, v, positions, positions,
                                 cfg.attn_chunk_q,
                                 bwd_remat=cfg.attn_bwd_remat)
    out2 = out.reshape(B, S, -1)
    if out2.shape[-1] != p["wo"].shape[0]:   # serve TP: concat local heads
        out2 = gather_tp(out2, -1)
    y = jnp.einsum("bsh,hd->bsd", out2, p["wo"].astype(cdt))
    if y.shape[-1] != cfg.d_model:           # serve TP: concat wo columns
        y = gather_tp(y, -1)
    y = constrain(y, "dp", None, None)
    if return_kv:
        return y, (k, v)
    return y


def paged_decode_attention(p, x, cfg: ModelConfig, pool_kv, layer, tables,
                           pos, active, impl: Optional[str] = None):
    """One-token decode against ONE layer's paged KV pool (the
    paged-attention read path of the continuous-batching engine; the
    contiguous :func:`decode_attention` stays as the reference).

    x: (B, 1, D); pool_kv: (N, L, 2, R, block, W) — the whole paged K/V
    pool (:func:`repro.serve.kvcache.init_kv_pool`); layer: int32 scalar,
    this layer's index into it (the new token's K and V land in one fused
    in-place write, and the read addresses the layer itself, so no
    per-layer slice is copied); tables:
    (B, max_blocks) int32 block tables (tail entries point at the sink
    block); pos: (B,) int32 PER-ROW positions — rows of a continuously
    batched decode sit at different sequence lengths, which is exactly what
    the contiguous cache's single scalar ``pos`` cannot express; active:
    (B,) bool — masked rows write their KV to the sink and their output is
    discarded by the engine. Returns (y (B, 1, D), pool_kv).

    impl selects the read path (must be trace-static):

    * ``"pallas"`` — the gather-free Pallas kernel of
      :mod:`repro.kernels.paged_attention` (Mosaic on TPU, interpreter
      elsewhere): pages are read in place through the scalar-prefetched
      block table and blocks past each row's length are skipped.
    * ``"xla"``    — the same blockwise algorithm as a traced-bound page
      loop (the non-TPU fast path).
    * ``"gather"`` — the original materialize-then-mask path over the
      fully padded span: O(max_blocks) per row regardless of length. Kept
      as the reference oracle the kernels are tested against.
    * None         — :func:`repro.kernels.ops.default_paged_impl`.
    """
    from ..kernels.ops import default_paged_impl, paged_attention
    from ..serve.kvcache import append_kv, gather_read_attention

    if impl is None:
        impl = default_paged_impl()
    B, _, D = x.shape
    hd = cfg.hd
    H = p["wq"].shape[-1] // hd        # local head count under serve TP
    cdt = dtype_of(cfg.compute_dtype)
    q, k, v = _project_qkv(p, x, cfg, pos[:, None])
    pool_kv = append_kv(pool_kv, layer, k[:, 0], v[:, 0], tables, pos,
                        active)
    if impl == "gather":
        out = gather_read_attention(q.reshape(B, H, hd), pool_kv, layer,
                                    tables, pos)
    else:
        out = paged_attention(q.reshape(B, H, hd), pool_kv, tables, pos,
                              layer, impl=impl)
    out2 = out.reshape(B, H * hd).astype(cdt)
    if out2.shape[-1] != p["wo"].shape[0]:   # serve TP: concat local heads
        out2 = gather_tp(out2, -1)
    y = jnp.einsum("bh,hd->bd", out2, p["wo"].astype(cdt))
    if y.shape[-1] != cfg.d_model:           # serve TP: concat wo columns
        y = gather_tp(y, -1)
    return y[:, None, :], pool_kv


def paged_prefill_window_attention(p, x, cfg: ModelConfig, pool_kv, layer,
                                   tables, positions, valid):
    """One chunked-prefill WINDOW against one layer's paged KV pool.

    The two-phase-admission engine feeds a prompt into the pool in
    fixed-size windows across successive pipeline cycles; each window's K/V
    is scattered through the row's block table and its queries then attend
    to the row's full paged prefix (earlier windows included) plus the
    causal part of the window itself. The read is the gather path — prefill
    runs once per window, not once per generated token, so the
    materializing read is fine here; the per-token decode hot path stays on
    the gather-free kernels.

    x: (B, C, D) window hidden states; pool_kv: (N, L, 2, R, block, W)
    the whole pool, written in place at ``layer`` (int32 scalar); tables:
    (B, max_blocks) int32; positions: (B, C) int32 absolute positions
    ``start[b] + c``; valid: (B, C) bool — False entries (rows not
    prefilling, window tail past the prompt) are not written and their
    outputs are junk the engine never reads. Valid entries
    always form a per-row prefix, so a valid query's causal span
    ``kpos <= positions[b, c]`` is fully populated. Returns (y (B, C, D),
    pool_kv).
    """
    from ..serve.kvcache import gather_pages, scatter_token_window

    B, C, D = x.shape
    hd = cfg.hd
    H = p["wq"].shape[-1] // hd        # local head counts under serve TP
    KV = p["wk"].shape[-1] // hd
    G = H // KV
    cdt = dtype_of(cfg.compute_dtype)
    q, k, v = _project_qkv(p, x, cfg, positions)
    pool_kv = scatter_token_window(pool_kv, layer, k, v, tables,
                                   positions[:, 0], valid)
    ks, vs = gather_pages(pool_kv, layer, tables, hd)  # (B, KV, T, hd)
    T = ks.shape[2]
    qg = q.reshape(B, C, KV, G, hd)
    s = jnp.einsum("bckgh,bksh->bkgcs", qg, ks,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    kpos = jnp.arange(T, dtype=jnp.int32)
    mask = kpos[None, None, None, None, :] <= positions[:, None, None, :, None]
    s = jnp.where(mask, s, NEG_INF)
    pmax = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - pmax)
    probs = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(vs.dtype)
    out = jnp.einsum("bkgcs,bksh->bckgh", probs, vs)
    out2 = out.reshape(B, C, H * hd).astype(cdt)
    if out2.shape[-1] != p["wo"].shape[0]:   # serve TP: concat local heads
        out2 = gather_tp(out2, -1)
    y = jnp.einsum("bch,hd->bcd", out2, p["wo"].astype(cdt))
    if y.shape[-1] != cfg.d_model:           # serve TP: concat wo columns
        y = gather_tp(y, -1)
    return y, pool_kv


def decode_attention_rows(p, x, cfg: ModelConfig, cache_k, cache_v, pos):
    """Per-row-position variant of :func:`decode_attention` for the
    slot-resident hybrid (zamba2) shared block: rows of a continuously
    batched decode sit at different sequence positions, so each row writes
    its token at its OWN ``pos[b]`` and masks its OWN causal extent — the
    contiguous path's single scalar ``pos`` cannot express that.

    x: (B, 1, D); cache_[kv]: (B, KV, S_max, hd) slot-pool caches (each slot
    owns a fixed contiguous span — attention state here is the per-slot pool
    entry, not a paged table); pos: (B,) int32. Returns (y, cache_k,
    cache_v). Row-wise math: a row's output depends only on its own cache
    row, so resident rows are bit-identical to the grouped per-call path.
    """
    B, _, D = x.shape
    hd = cfg.hd
    H = p["wq"].shape[-1] // hd        # local head counts under serve TP
    KV = p["wk"].shape[-1] // hd
    G = H // KV
    S_max = cache_k.shape[2]
    q, k, v = _project_qkv(p, x, cfg, pos[:, None])
    cdt = dtype_of(cfg.compute_dtype)
    bidx = jnp.arange(B, dtype=jnp.int32)
    cache_k = cache_k.at[bidx, :, pos].set(k[:, 0].astype(cache_k.dtype))
    cache_v = cache_v.at[bidx, :, pos].set(v[:, 0].astype(cache_v.dtype))
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bksh->bkgs", qg, cache_k,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    kpos = jnp.arange(S_max, dtype=jnp.int32)
    s = jnp.where((kpos[None, :] <= pos[:, None])[:, None, None, :],
                  s, NEG_INF)
    pmax = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - pmax)
    probs = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(cache_v.dtype)
    out = jnp.einsum("bkgs,bksh->bkgh", probs, cache_v)
    out2 = out.reshape(B, H * hd).astype(cdt)
    if out2.shape[-1] != p["wo"].shape[0]:   # serve TP: concat local heads
        out2 = gather_tp(out2, -1)
    y = jnp.einsum("bh,hd->bd", out2, p["wo"].astype(cdt))
    if y.shape[-1] != cfg.d_model:           # serve TP: concat wo columns
        y = gather_tp(y, -1)
    return y[:, None, :], cache_k, cache_v


def decode_attention(p, x, cfg: ModelConfig, cache_k, cache_v, pos):
    """One-token decode against a KV cache.

    x: (B, 1, D); cache_[kv]: (B, KV, S_max, hd) — S_max is sharded over the
    ``sp`` axis for long contexts (sequence-parallel cache; the softmax
    reductions over the sharded S dim lower to cross-shard collectives,
    the flash-decode pattern). pos: scalar int32 current position.
    Returns (y, cache_k, cache_v) with the new token written at ``pos``.
    """
    B, _, D = x.shape
    hd = cfg.hd
    H = p["wq"].shape[-1] // hd        # local head counts under serve TP
    KV = p["wk"].shape[-1] // hd
    G = H // KV
    S_max = cache_k.shape[2]
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    q, k, v = _project_qkv(p, x, cfg, positions)
    cdt = dtype_of(cfg.compute_dtype)
    # write new kv at pos
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k.astype(cache_k.dtype).transpose(0, 2, 1, 3), pos, axis=2)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v.astype(cache_v.dtype).transpose(0, 2, 1, 3), pos, axis=2)
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,bksh->bkgs", qg, cache_k,
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    kpos = jnp.arange(S_max, dtype=jnp.int32)
    s = jnp.where((kpos <= pos)[None, None, None, :], s, NEG_INF)
    pmax = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - pmax)
    probs = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(cache_v.dtype)
    out = jnp.einsum("bkgs,bksh->bkgh", probs, cache_v)
    out2 = out.reshape(B, H * hd).astype(cdt)
    if out2.shape[-1] != p["wo"].shape[0]:   # serve TP: concat local heads
        out2 = gather_tp(out2, -1)
    y = jnp.einsum("bh,hd->bd", out2, p["wo"].astype(cdt))
    if y.shape[-1] != cfg.d_model:           # serve TP: concat wo columns
        y = gather_tp(y, -1)
    return y[:, None, :], cache_k, cache_v
