"""Unified decoder LM covering all 10 assigned architectures.

Families:
  dense / audio / vlm : [ln -> GQA attention] + [ln -> (SwiGLU|GELU) MLP]
  moe                 : attention + MoE FFN (optional shared experts /
                        dense-residual path)
  ssm                 : [ln -> Mamba1] blocks, attention-free
  hybrid (zamba2)     : groups of `hybrid_attn_every` Mamba2 layers, each
                        group followed by ONE SHARED transformer block whose
                        weights are reused across groups, fed with
                        concat([hidden, embeddings]) @ fused_proj

Layer stacks are scan-over-layers (stacked params, `jax.lax.scan`) with
optional remat — this keeps HLO size O(1) in depth, which is what makes the
512-device dry-run compiles tractable.

Entry points (all pure):
  init_params / forward / loss_fn                      (training)
  init_cache / prefill / decode_step                   (serving)
Modality frontends are STUBS per the assignment: `frontend_embeds`
(B, frontend_tokens, d_model) arrive precomputed (see launch.dryrun
input_specs) and are prepended to the token embeddings.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..distributed.sharding import (constrain, manual_serve_map,
                                    serve_attn_sharded, serve_kv_cache_spec,
                                    serve_param_specs, serve_pool_spec,
                                    serve_tp_size)
from .attention import (attention, decode_attention, decode_attention_rows,
                        init_attention)
from .layers import dtype_of, normal_init, rms_norm, sinusoidal_positions
from .mamba import init_mamba, init_mamba_state, mamba_forward, mamba_step
from .mlp import init_mlp, mlp
from .moe import init_moe, moe_layer

__all__ = ["init_params", "forward", "loss_fn", "init_cache", "prefill",
           "prefill_window_paged", "decode_step", "decode_step_paged",
           "decode_step_slots", "decode_chunk_paged", "decode_chunk_slots"]


# ------------------------------------------------------------------ init
def _init_block(key, cfg: ModelConfig) -> Dict[str, Any]:
    """One repeated layer's params (flat dict: path-based sharding rules)."""
    pdt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    p: Dict[str, Any] = {"ln1": jnp.ones((cfg.d_model,), pdt)}
    if cfg.ssm:
        p.update(init_mamba(ks[0], cfg))
        return p
    p.update(init_attention(ks[0], cfg))
    p["ln2"] = jnp.ones((cfg.d_model,), pdt)
    if cfg.moe:
        p.update(init_moe(ks[1], cfg))
    else:
        p.update(init_mlp(ks[1], cfg))
    return p


def _init_shared_block(key, cfg: ModelConfig) -> Dict[str, Any]:
    """zamba2 shared transformer block (reused across groups)."""
    pdt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    from .layers import dense_init
    p = {"fused_proj": dense_init(ks[0], (2 * cfg.d_model, cfg.d_model), pdt),
         "ln1": jnp.ones((cfg.d_model,), pdt),
         "ln2": jnp.ones((cfg.d_model,), pdt)}
    p.update(init_attention(ks[1], cfg))
    p.update(init_mlp(ks[2], cfg))
    return p


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    pdt = dtype_of(cfg.param_dtype)
    k_embed, k_blocks, k_head, k_shared = jax.random.split(key, 4)
    Vp = cfg.padded_vocab
    params: Dict[str, Any] = {
        "embed": normal_init(k_embed, (Vp, cfg.d_model), 0.02, pdt),
        "final_norm": jnp.ones((cfg.d_model,), pdt),
    }
    L = cfg.num_layers
    if cfg.hybrid_attn_every:
        every = cfg.hybrid_attn_every
        G, tail = L // every, L % every
        kg = jax.random.split(k_blocks, G * every).reshape(G, every, 2)
        params["gblocks"] = jax.vmap(jax.vmap(
            lambda k: _init_block(k, cfg)))(kg)
        if tail:
            kt = jax.random.split(jax.random.fold_in(k_blocks, 1), tail)
            params["tail_blocks"] = jax.vmap(
                lambda k: _init_block(k, cfg))(kt)
        params["shared_block"] = _init_shared_block(k_shared, cfg)
    else:
        kb = jax.random.split(k_blocks, L)
        params["blocks"] = jax.vmap(lambda k: _init_block(k, cfg))(kb)
    if not cfg.tie_embeddings:
        params["lm_head"] = normal_init(k_head, (cfg.d_model, Vp), 0.02, pdt)
    return params


# ------------------------------------------------------------------ blocks
def _block_apply(p, x, cfg: ModelConfig, positions):
    """One layer, full-sequence (train/prefill w/o cache). Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["ln1"], cfg.rms_eps)
    if cfg.ssm:
        x = x + mamba_forward(p, h, cfg)
        return x, aux
    x = x + attention(p, h, cfg, positions)
    h2 = rms_norm(x, p["ln2"], cfg.rms_eps)
    if cfg.moe:
        y, aux = moe_layer(p, h2, cfg)
        x = x + y
    else:
        x = x + mlp(p, h2, cfg)
    return x, aux


def _shared_block_apply(p, x, x0, cfg: ModelConfig, positions):
    """zamba2 shared block: concat([x, x0]) -> proj -> attn -> mlp."""
    cdt = dtype_of(cfg.compute_dtype)
    h = jnp.concatenate([x, x0], axis=-1) @ p["fused_proj"].astype(cdt)
    a = attention(p, rms_norm(h, p["ln1"], cfg.rms_eps), cfg, positions)
    h = h + a
    h = h + mlp(p, rms_norm(h, p["ln2"], cfg.rms_eps), cfg)
    return x + h


def _stack_scan(stacked, x, cfg, positions, remat: bool):
    """lax.scan over a stacked layer dict; accumulates MoE aux."""

    def body(carry, layer_p):
        xx, aux = carry
        xx = constrain(xx, "dp", None, None)
        xx, a = _block_apply(layer_p, xx, cfg, positions)
        return (xx, aux + a), None

    fn = jax.checkpoint(body,
                        policy=jax.checkpoint_policies.nothing_saveable) \
        if remat else body
    (x, aux), _ = jax.lax.scan(fn, (x, jnp.zeros((), jnp.float32)), stacked)
    return x, aux


# ------------------------------------------------------------------ forward
def _embed(cfg: ModelConfig, params, tokens, frontend_embeds):
    cdt = dtype_of(cfg.compute_dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
    if cfg.frontend != "none":
        if frontend_embeds is None:
            raise ValueError(f"{cfg.name} requires frontend_embeds "
                             f"({cfg.frontend} stub)")
        x = jnp.concatenate([frontend_embeds.astype(cdt), x], axis=1)
    B, S = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    if cfg.pos_emb == "sinusoidal":
        x = x + sinusoidal_positions(positions, cfg.d_model).astype(cdt)
    return x, positions


def forward(cfg: ModelConfig, params, tokens,
            frontend_embeds=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Teacher-forced logits over the full (frontend + token) sequence.
    Returns (logits_f32 (B, S_total, padded_vocab), moe_aux_loss)."""
    x, positions = _embed(cfg, params, tokens, frontend_embeds)
    x = constrain(x, "dp", None, None)
    aux = jnp.zeros((), jnp.float32)
    if cfg.hybrid_attn_every:
        x0 = x
        sb = params["shared_block"]

        def group(carry, gp):
            xx, aux = carry
            (xx, a), _ = jax.lax.scan(
                lambda c, lp: (( _block_apply(lp, c[0], cfg, positions)[0],
                                 c[1]), None),
                (xx, aux), gp)
            xx = _shared_block_apply(sb, xx, x0, cfg, positions)
            return (xx, a), None

        gfn = jax.checkpoint(group,
                             policy=jax.checkpoint_policies.nothing_saveable)\
            if cfg.remat else group
        (x, aux), _ = jax.lax.scan(gfn, (x, aux), params["gblocks"])
        if "tail_blocks" in params:
            x, a2 = _stack_scan(params["tail_blocks"], x, cfg, positions,
                                cfg.remat)
            aux = aux + a2
    else:
        x, aux = _stack_scan(params["blocks"], x, cfg, positions, cfg.remat)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x,
                        head.astype(dtype_of(cfg.compute_dtype)),
                        preferred_element_type=jnp.float32)
    logits = constrain(logits, "dp", None, "tp")
    return logits, aux


def loss_fn(cfg: ModelConfig, params, batch) -> Tuple[jnp.ndarray, Dict]:
    """Next-token cross-entropy (fp32, padded-vocab masked) + MoE aux."""
    tokens = batch["tokens"]
    fe = batch.get("frontend_embeds")
    logits, aux = forward(cfg, params, tokens, fe)
    F = cfg.frontend_tokens if cfg.frontend != "none" else 0
    S = tokens.shape[1]
    if F > 0:
        pred = logits[:, F - 1:F + S - 1]     # predict t_0..t_{S-1}
        labels = tokens
    else:
        pred = logits[:, :S - 1]
        labels = tokens[:, 1:]
    vmask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
    pred = jnp.where(vmask, pred, -1e30)
    logz = jax.nn.logsumexp(pred, axis=-1)
    # NOTE: gather(labels) over the vocab-sharded logits would force an
    # all-gather of the full (B,S,V) tensor; the equality-mask reduction
    # keeps the contraction sharded over `tp` (saved ~24GB/dev, see
    # EXPERIMENTS.md §Perf iteration log).
    onehot = labels[..., None] == jnp.arange(cfg.padded_vocab,
                                             dtype=labels.dtype)
    gold = jnp.sum(jnp.where(onehot, pred, 0.0), axis=-1)
    ce = jnp.mean(logz - gold)
    zloss = 1e-4 * jnp.mean(jnp.square(logz))
    total = ce + zloss + cfg.router_aux_weight * aux
    return total, {"ce": ce, "aux": aux, "zloss": zloss,
                   "ppl_proxy": jnp.exp(jnp.minimum(ce, 20.0))}


# ------------------------------------------------------------------ serving
def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Decode cache pytree. Attention: (L, B, KV, S_max, hd) KV tensors,
    sequence dim shardable over `sp`. SSM: per-layer (conv_buf, h)."""
    cdt = dtype_of(cfg.compute_dtype)
    L = cfg.num_layers
    cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.hybrid_attn_every:
        every = cfg.hybrid_attn_every
        G, tail = L // every, L % every
        conv, h = init_mamba_state(cfg, batch, cdt)
        cache["g_ssm"] = (
            jnp.tile(conv[None, None], (G, every) + (1,) * conv.ndim),
            jnp.tile(h[None, None], (G, every) + (1,) * h.ndim))
        if tail:
            cache["tail_ssm"] = (
                jnp.tile(conv[None], (tail,) + (1,) * conv.ndim),
                jnp.tile(h[None], (tail,) + (1,) * h.ndim))
        KV, hd = cfg.num_kv_heads, cfg.hd
        cache["shared_k"] = jnp.zeros((G, batch, KV, max_len, hd), cdt)
        cache["shared_v"] = jnp.zeros((G, batch, KV, max_len, hd), cdt)
    elif cfg.ssm:
        conv, h = init_mamba_state(cfg, batch, cdt)
        cache["ssm"] = (
            jnp.tile(conv[None], (L,) + (1,) * conv.ndim),
            jnp.tile(h[None], (L,) + (1,) * h.ndim))
    else:
        KV, hd = cfg.num_kv_heads, cfg.hd
        cache["k"] = jnp.zeros((L, batch, KV, max_len, hd), cdt)
        cache["v"] = jnp.zeros((L, batch, KV, max_len, hd), cdt)
    return cache


def _block_decode(p, x1, cfg: ModelConfig, layer_cache, pos, attn_fn=None):
    """One layer, one token. x1: (B, D). Returns (x1, new_layer_cache).

    ``attn_fn(p, h1, layer_cache) -> (y, new_layer_cache)`` swaps the
    attention/cache implementation (the paged path passes one reading
    through a block table); everything around it — ln1, residuals, ln2,
    MoE/MLP — is shared, so the paged and contiguous decode paths cannot
    structurally diverge."""
    h = rms_norm(x1, p["ln1"], cfg.rms_eps)
    if cfg.ssm:
        y, st = mamba_step(p, h, cfg, layer_cache)
        return x1 + y, st
    if attn_fn is None:
        ck, cv = layer_cache
        y, ck, cv = decode_attention(p, h[:, None, :], cfg, ck, cv, pos)
        layer_cache = (ck, cv)
    else:
        y, layer_cache = attn_fn(p, h[:, None, :], layer_cache)
    x1 = x1 + y[:, 0]
    h2 = rms_norm(x1, p["ln2"], cfg.rms_eps)
    if cfg.moe:
        y2, _ = moe_layer(p, h2[:, None, :], cfg)
        x1 = x1 + y2[:, 0]
    else:
        x1 = x1 + mlp(p, h2[:, None, :], cfg)[:, 0]
    return x1, layer_cache


def _shared_block_decode(p, x1, x0, cfg, ck, cv, pos):
    cdt = dtype_of(cfg.compute_dtype)
    h = jnp.concatenate([x1, x0], axis=-1) @ p["fused_proj"].astype(cdt)
    a, ck, cv = decode_attention(
        p, rms_norm(h, p["ln1"], cfg.rms_eps)[:, None, :], cfg, ck, cv, pos)
    h = h + a[:, 0]
    h = h + mlp(p, rms_norm(h, p["ln2"], cfg.rms_eps)[:, None, :],
                cfg)[:, 0]
    return x1 + h, ck, cv


def decode_step(cfg: ModelConfig, params, cache, token
                ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One decode step. token: (B,) int32 current input token.
    Returns (logits (B, padded_vocab) f32, updated cache)."""
    cdt = dtype_of(cfg.compute_dtype)
    pos = cache["pos"]
    x1 = jnp.take(params["embed"], token, axis=0).astype(cdt)
    if cfg.pos_emb == "sinusoidal":
        x1 = x1 + sinusoidal_positions(pos[None], cfg.d_model
                                       ).astype(cdt)[0]
    new_cache = dict(cache)
    if cfg.hybrid_attn_every:
        x0 = x1
        sb = params["shared_block"]

        def group(carry, xs):
            xx = carry
            gp, g_ssm, ck, cv = xs

            def layer(c, l_xs):
                lp, st = l_xs
                c, st = _block_decode(lp, c, cfg, st, pos)
                return c, st

            xx, g_ssm = jax.lax.scan(layer, xx, (gp, g_ssm))
            xx, ck, cv = _shared_block_decode(sb, xx, x0, cfg, ck, cv, pos)
            return xx, (g_ssm, ck, cv)

        x1, (g_ssm, sk, sv) = jax.lax.scan(
            group, x1, (params["gblocks"], cache["g_ssm"],
                        cache["shared_k"], cache["shared_v"]))
        new_cache["g_ssm"], new_cache["shared_k"], new_cache["shared_v"] = \
            g_ssm, sk, sv
        if "tail_blocks" in params:
            def layer(c, l_xs):
                lp, st = l_xs
                return _block_decode(lp, c, cfg, st, pos)

            x1, tail = jax.lax.scan(layer, x1,
                                    (params["tail_blocks"],
                                     cache["tail_ssm"]))
            new_cache["tail_ssm"] = tail
    elif cfg.ssm:
        def layer(c, l_xs):
            lp, st = l_xs
            return _block_decode(lp, c, cfg, st, pos)

        x1, ssm = jax.lax.scan(layer, x1, (params["blocks"], cache["ssm"]))
        new_cache["ssm"] = ssm
    else:
        def layer(c, l_xs):
            lp, ck, cv = l_xs
            c, (ck, cv) = _block_decode(lp, c, cfg, (ck, cv), pos)
            return c, (ck, cv)

        x1, (k, v) = jax.lax.scan(layer, x1,
                                  (params["blocks"], cache["k"], cache["v"]))
        new_cache["k"], new_cache["v"] = k, v
    x1 = rms_norm(x1, params["final_norm"], cfg.rms_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bd,dv->bv", x1, head.astype(cdt),
                        preferred_element_type=jnp.float32)
    new_cache["pos"] = pos + 1
    return logits, new_cache


def _shared_block_decode_rows(p, x1, x0, cfg, ck, cv, pos):
    """Per-row-position zamba2 shared block (slot-resident decode)."""
    cdt = dtype_of(cfg.compute_dtype)
    h = jnp.concatenate([x1, x0], axis=-1) @ p["fused_proj"].astype(cdt)
    a, ck, cv = decode_attention_rows(
        p, rms_norm(h, p["ln1"], cfg.rms_eps)[:, None, :], cfg, ck, cv, pos)
    h = h + a[:, 0]
    h = h + mlp(p, rms_norm(h, p["ln2"], cfg.rms_eps)[:, None, :],
                cfg)[:, 0]
    return x1 + h, ck, cv


def decode_step_slots(cfg: ModelConfig, params, state, token, pos
                      ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One decode step over the SLOT-RESIDENT state pool of an SSM/hybrid
    architecture — the recurrent-state counterpart of
    :func:`decode_step_paged`, with PER-ROW positions.

    The continuous-batching engine keeps one fixed-slot pool of recurrent
    state (mamba ``(conv_buf, h)`` per layer; zamba2 additionally the shared
    block's per-slot KV span): sequences claim a slot at admission (their
    prefilled state is scattered in), decode side by side at their own
    positions, and release the slot at retirement. ``state`` is
    :func:`init_cache`'s pytree minus the scalar ``pos`` (replaced by the
    per-row ``pos`` argument). Every op here is row-wise — no cross-batch
    reduction — so a resident row's tokens are bit-identical to the grouped
    per-call path regardless of who shares the batch; inactive slots step on
    stale state harmlessly (their output is discarded host-side, their slot
    is overwritten at the next admission).

    This mirrors :func:`decode_step`'s scan skeleton with the scalar
    ``cache["pos"]`` replaced by the per-row argument (the per-layer math is
    shared through :func:`_block_decode`); a change to the embed / final
    norm / lm-head framing there must be mirrored here, or the slot path
    silently diverges from the reference it is tested against.

    token: (B,) int32 current input token; pos: (B,) int32 per-row position.
    Returns (logits (B, padded_vocab) f32, new state).
    """
    if not (cfg.ssm or cfg.hybrid_attn_every):
        raise ValueError(f"{cfg.name}: slot-state decode is the SSM/hybrid "
                         "path; attention archs page their KV instead")
    cdt = dtype_of(cfg.compute_dtype)
    x1 = jnp.take(params["embed"], token, axis=0).astype(cdt)
    if cfg.pos_emb == "sinusoidal":
        x1 = x1 + sinusoidal_positions(pos, cfg.d_model).astype(cdt)
    new_state = dict(state)
    if cfg.hybrid_attn_every:
        x0 = x1
        sb = params["shared_block"]

        def group(carry, xs):
            xx = carry
            gp, g_ssm, ck, cv = xs

            def layer(c, l_xs):
                lp, st = l_xs
                c, st = _block_decode(lp, c, cfg, st, pos)
                return c, st

            xx, g_ssm = jax.lax.scan(layer, xx, (gp, g_ssm))
            xx, ck, cv = _shared_block_decode_rows(sb, xx, x0, cfg, ck, cv,
                                                   pos)
            return xx, (g_ssm, ck, cv)

        x1, (g_ssm, sk, sv) = jax.lax.scan(
            group, x1, (params["gblocks"], state["g_ssm"],
                        state["shared_k"], state["shared_v"]))
        new_state["g_ssm"], new_state["shared_k"], new_state["shared_v"] = \
            g_ssm, sk, sv
        if "tail_blocks" in params:
            def layer(c, l_xs):
                lp, st = l_xs
                return _block_decode(lp, c, cfg, st, pos)

            x1, tail = jax.lax.scan(layer, x1,
                                    (params["tail_blocks"],
                                     state["tail_ssm"]))
            new_state["tail_ssm"] = tail
    else:
        def layer(c, l_xs):
            lp, st = l_xs
            return _block_decode(lp, c, cfg, st, pos)

        x1, ssm = jax.lax.scan(layer, x1, (params["blocks"], state["ssm"]))
        new_state["ssm"] = ssm
    x1 = rms_norm(x1, params["final_norm"], cfg.rms_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bd,dv->bv", x1, head.astype(cdt),
                        preferred_element_type=jnp.float32)
    return logits, new_state


def decode_step_paged(cfg: ModelConfig, params, pool_kv, tables,
                      lengths, token, active, impl: Optional[str] = None
                      ) -> Tuple[jnp.ndarray, Any]:
    """One decode step through a paged KV cache (continuous batching).

    Unlike :func:`decode_step`, every batch row carries its OWN position:
    ``lengths[b]`` is where row ``b``'s next KV entry lands and how far its
    causal mask extends — rows admitted at different times decode side by
    side. The pool layout and scatter helpers live in
    :mod:`repro.serve.kvcache`; the contiguous path above remains the
    reference implementation (the two agree token-for-token under greedy
    decoding, see ``tests/test_serve_continuous.py``).

    pool_kv: (N, L, 2, R, block, W) paged K/V pool; tables:
    (B, max_blocks) int32; lengths: (B,) int32; token: (B,) int32; active:
    (B,) bool (inactive rows write KV to the sink block and their logits
    are discarded). ``impl`` (trace-static) picks the attention read path —
    the gather-free kernel/page-loop or the materializing ``"gather"``
    oracle; see :func:`repro.models.attention.paged_decode_attention`.
    Returns (logits (B, padded_vocab) f32, pool_kv).
    Attention architectures only — SSM/hybrid states are O(1) per sequence
    and take the contiguous path.
    """
    if cfg.ssm or cfg.hybrid_attn_every:
        raise ValueError(f"{cfg.name}: paged decode requires a pure "
                         "attention architecture")
    from .attention import paged_decode_attention

    cdt = dtype_of(cfg.compute_dtype)
    pos = lengths
    x1 = jnp.take(params["embed"], token, axis=0).astype(cdt)
    if cfg.pos_emb == "sinusoidal":
        x1 = x1 + sinusoidal_positions(pos, cfg.d_model).astype(cdt)

    def paged_attn(lp, h1, layer_cache):
        pkv, li = layer_cache
        y, pkv = paged_decode_attention(lp, h1, cfg, pkv, li, tables, pos,
                                        active, impl=impl)
        return y, (pkv, li)

    # the pool rides the layer loop as CARRY and each layer updates its
    # pages in place: as scan xs/ys every step would write a new stacked
    # pool (a pool-sized temporary)
    def layer(c, l_xs):
        x, pkv = c
        lp, li = l_xs
        x, (pkv, _) = _block_decode(lp, x, cfg, (pkv, li), pos,
                                    attn_fn=paged_attn)
        return (x, pkv), None

    (x1, pool_kv), _ = jax.lax.scan(
        layer, (x1, pool_kv),
        (params["blocks"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    x1 = rms_norm(x1, params["final_norm"], cfg.rms_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bd,dv->bv", x1, head.astype(cdt),
                        preferred_element_type=jnp.float32)
    return logits, pool_kv


def _decode_chunk_scan(step, state, carry, n: int):
    """Shared chunk loop of :func:`decode_chunk_paged` /
    :func:`decode_chunk_slots`: ``n`` greedy steps of ``step(state, tok,
    lengths, active) -> (logits, state)`` threading the device carry.
    Rows with ``rem == 0`` are inactive: their token repeats (stable
    carry) and the engine discards their emitted tokens host-side."""
    lengths, last, rem = carry

    def body(c, _):
        st, tok, ln, rm = c
        active = rm > 0
        logits, st = step(st, tok, ln, active)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(active, nxt, tok)
        ln = ln + active.astype(jnp.int32)
        rm = rm - active.astype(jnp.int32)
        return (st, nxt, ln, rm), nxt

    (state, tok, ln, rm), toks = jax.lax.scan(
        body, (state, last, lengths, rem), None, length=n)
    return state, (ln, tok, rm), toks.swapaxes(0, 1)


def _serve_tp_active(cfg: ModelConfig, ctx) -> bool:
    """True when lm entry points should run under shard_map serve TP."""
    return ctx is not None and serve_tp_size(ctx) > 1


def decode_chunk_paged(cfg: ModelConfig, params, pool_kv, tables, carry,
                       n: int, impl: Optional[str] = None, ctx=None):
    """``n`` greedy paged decode steps over the resident batch in one traced
    loop — the chunk program of the continuous-batching engine.

    ``carry = (lengths, last, rem)`` is the DEVICE-RESIDENT decode carry:
    per-row KV length / last emitted token / decode steps remaining. The
    async-lookahead engine feeds chunk N's output carry straight into chunk
    N+1 without a host round-trip, so the device-side dependency chain never
    waits on host scheduling; the synchronous engine passes uploaded host
    mirrors through the SAME function (one compiled program serves both
    modes). Inactive rows' KV writes go to the sink block.

    Returns ``(pool_kv, (lengths, last, rem), toks)`` with ``toks`` the
    ``(B, n)`` greedy tokens (rows active for ``k < n`` steps repeat their
    final token in the tail — the host takes ``toks[b, :k]``).

    ``ctx``: optional ShardCtx — with a multi-device ``model`` axis the
    chunk runs under shard_map (KV-head-sharded pool and weights, exact-bit
    TP; tables/carry/tokens replicated). The Pallas/XLA paged read kernels
    are untouched: every shape they see is just the per-shard KV slice.
    """
    if _serve_tp_active(cfg, ctx):
        pspec = serve_param_specs(cfg, params, ctx)
        pool = serve_pool_spec(cfg, ctx)
        R = P()

        def run(prm, pkv, tbl, ln, last, rem):
            return decode_chunk_paged(cfg, prm, pkv, tbl, (ln, last, rem),
                                      n, impl=impl)

        f = manual_serve_map(run, ctx,
                             in_specs=(pspec, pool, R, R, R, R),
                             out_specs=(pool, (R, R, R), R))
        return f(params, pool_kv, tables, *carry)

    def step(pkv, tok, ln, active):
        return decode_step_paged(cfg, params, pkv, tables, ln, tok, active,
                                 impl=impl)

    return _decode_chunk_scan(step, pool_kv, carry, n)


def decode_chunk_slots(cfg: ModelConfig, params, state, carry, n: int,
                       ctx=None):
    """``n`` greedy decode steps over the SSM/hybrid slot-state pool — the
    recurrent-state counterpart of :func:`decode_chunk_paged`, with the same
    device-resident ``(lengths, last, rem)`` carry contract (chunk N+1 can
    consume chunk N's carry without a host sync). Inactive slots step on
    stale state harmlessly (row-wise math; tokens discarded host-side).

    Returns ``(state, (lengths, last, rem), toks)``.

    ``ctx``: optional ShardCtx — SSM/hybrid slot state and weights stay
    fully replicated on a mesh (per-shard compute is identical, hence
    trivially bit-exact); the shard_map wrap keeps the engine's data flow
    uniform with the paged path.
    """
    if _serve_tp_active(cfg, ctx):
        R = P()

        def run(prm, st, ln, last, rem):
            return decode_chunk_slots(cfg, prm, st, (ln, last, rem), n)

        f = manual_serve_map(run, ctx, in_specs=(R, R, R, R, R),
                             out_specs=(R, (R, R, R), R))
        return f(params, state, *carry)

    def step(st, tok, ln, active):
        return decode_step_slots(cfg, params, st, tok, ln)

    return _decode_chunk_scan(step, state, carry, n)


def _block_window(p, x, cfg: ModelConfig, attn_fn, layer_cache):
    """One layer over a chunked-prefill window. Mirrors :func:`_block_apply`
    with the attention swapped for a paged read/write through ``attn_fn(p,
    h, layer_cache) -> (y, layer_cache)`` — ln1/residual/ln2/MoE-or-MLP stay
    shared so the window path cannot structurally diverge from full
    prefill."""
    h = rms_norm(x, p["ln1"], cfg.rms_eps)
    y, layer_cache = attn_fn(p, h, layer_cache)
    x = x + y
    h2 = rms_norm(x, p["ln2"], cfg.rms_eps)
    if cfg.moe:
        y2, _ = moe_layer(p, h2, cfg)
        x = x + y2
    else:
        x = x + mlp(p, h2, cfg)
    return x, layer_cache


def prefill_window_paged(cfg: ModelConfig, params, pool_kv, tables, tokens,
                         start, valid, last_idx, ctx=None
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Process one chunked-prefill WINDOW for every mid-prefill row of the
    resident batch, writing the window's KV straight into the paged pool.

    This is the second half of two-phase admission's chunked prefill: the
    engine admits a prompt on its prompt-only footprint, scatters its first
    window at the merge, and then feeds the remainder through THIS function
    one fixed-size window per pipeline cycle — resident rows keep decoding
    in the overlapped cycles, and because the window shape ``(B, C)`` never
    depends on prompt lengths, mixed-length admission groups share one
    compiled program.

    pool_kv: (N, L, 2, R, block, W); tables: (B, max_blocks) int32;
    tokens: (B, C) int32 window tokens (invalid entries arbitrary); start:
    (B,) int32 per-row window origin (absolute position of window column 0);
    valid: (B, C) bool (False for rows not prefilling and past-prompt
    tails); last_idx: (B,) int32 window column of each row's FINAL prompt
    token, clipped into range — its logits seed the row's first generated
    token, consumed only for rows whose prompt ends in this window.
    Returns (first_tokens (B,) int32 greedy, pool_kv). Attention archs only.
    """
    if cfg.ssm or cfg.hybrid_attn_every:
        raise ValueError(f"{cfg.name}: paged chunked prefill requires a "
                         "pure attention architecture")
    if _serve_tp_active(cfg, ctx):
        pspec = serve_param_specs(cfg, params, ctx)
        pool = serve_pool_spec(cfg, ctx)
        R = P()

        def run(prm, pkv, tbl, tk, st, vd, li):
            return prefill_window_paged(cfg, prm, pkv, tbl, tk, st, vd, li)

        f = manual_serve_map(run, ctx,
                             in_specs=(pspec, pool, R, R, R, R, R),
                             out_specs=(R, pool))
        return f(params, pool_kv, tables, tokens, start, valid, last_idx)
    from .attention import paged_prefill_window_attention

    cdt = dtype_of(cfg.compute_dtype)
    B, C = tokens.shape
    positions = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
    if cfg.pos_emb == "sinusoidal":
        x = x + sinusoidal_positions(positions, cfg.d_model).astype(cdt)

    def win_attn(lp, h, layer_cache):
        pkv, li = layer_cache
        y, pkv = paged_prefill_window_attention(lp, h, cfg, pkv, li, tables,
                                                positions, valid)
        return y, (pkv, li)

    # pool as layer-loop carry, updated in place (see decode_step_paged)
    def layer(c, l_xs):
        xx, pkv = c
        lp, li = l_xs
        xx, (pkv, _) = _block_window(lp, xx, cfg, win_attn, (pkv, li))
        return (xx, pkv), None

    (x, pool_kv), _ = jax.lax.scan(
        layer, (x, pool_kv),
        (params["blocks"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    x_last = x[jnp.arange(B), last_idx]
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bd,dv->bv", x_last, head.astype(cdt),
                        preferred_element_type=jnp.float32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), pool_kv


def prefill(cfg: ModelConfig, params, tokens, max_len: int = 0,
            frontend_embeds=None, last_positions=None, ctx=None):
    """Process a prompt, producing last-position logits + a primed cache.

    For attention archs the KV cache is computed per layer; for SSM archs
    the (conv, h) states are produced by the chunked scans. max_len=0 sizes
    the cache exactly at the prompt length (the dry-run prefill cell).
    ``last_positions`` ((B,) int32, optional) picks a PER-ROW logit
    position instead of the shared final one — mixed-length admission
    groups are right-padded to one window shape, so each row's first-token
    logits sit at its own prompt end.
    """
    if (_serve_tp_active(cfg, ctx) and frontend_embeds is None
            and serve_attn_sharded(cfg, serve_tp_size(ctx))):
        # serve TP: run the whole prefill under shard_map; the primed cache
        # k/v come back KV-head-sharded, ready for the engine's pool scatter
        pspec = serve_param_specs(cfg, params, ctx)
        kv = serve_kv_cache_spec(cfg, ctx)
        R = P()
        cache_spec = {"pos": R, "k": kv, "v": kv}
        args = [params, tokens]
        specs = [pspec, R]
        if last_positions is not None:
            args.append(last_positions)
            specs.append(R)

        def run(prm, tk, *rest):
            return prefill(cfg, prm, tk, max_len=max_len,
                           last_positions=rest[0] if rest else None)

        f = manual_serve_map(run, ctx, in_specs=tuple(specs),
                             out_specs=(R, cache_spec))
        return f(*args)
    B, S = tokens.shape[:2]
    F = cfg.frontend_tokens if cfg.frontend != "none" else 0
    if F and last_positions is not None:
        # last_positions indexes the CONCATENATED (frontend + token)
        # sequence; the serve engine only uses it on frontend-free archs,
        # and silently off-by-F logits would be worse than refusing
        raise ValueError(f"{cfg.name}: last_positions does not account for "
                         "the frontend prefix; offset by frontend_tokens "
                         "first")
    total = S + F
    max_len = max(max_len, total)
    x, positions = _embed(cfg, params, tokens, frontend_embeds)
    cache = init_cache(cfg, B, max_len)
    cdt = dtype_of(cfg.compute_dtype)

    if cfg.hybrid_attn_every:
        x0 = x
        sb = params["shared_block"]
        g_conv, g_h = [], []
        sks, svs = [], []
        G = cfg.num_layers // cfg.hybrid_attn_every
        gp_all = params["gblocks"]
        for g in range(G):  # python loop: G is small (<=6)
            gp = jax.tree_util.tree_map(lambda t: t[g], gp_all)
            convs, hs = [], []
            for l in range(cfg.hybrid_attn_every):
                lp = jax.tree_util.tree_map(lambda t: t[l], gp)
                h = rms_norm(x, lp["ln1"], cfg.rms_eps)
                y, (cv, hh) = mamba_forward(lp, h, cfg, return_state=True)
                x = x + y
                convs.append(cv)
                hs.append(hh)
            # shared block with kv capture
            hcat = jnp.concatenate([x, x0], axis=-1) \
                @ sb["fused_proj"].astype(cdt)
            a, (k, v) = attention(sb, rms_norm(hcat, sb["ln1"], cfg.rms_eps),
                                  cfg, positions, return_kv=True)
            hcat = hcat + a
            hcat = hcat + mlp(sb, rms_norm(hcat, sb["ln2"], cfg.rms_eps), cfg)
            x = x + hcat
            k = k.transpose(0, 2, 1, 3)  # (B,KV,S,hd)
            v = v.transpose(0, 2, 1, 3)
            pad = max_len - total
            if pad:
                k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
            sks.append(constrain(k.astype(cdt), "dp", None, "sp", None))
            svs.append(constrain(v.astype(cdt), "dp", None, "sp", None))
            g_conv.append(jnp.stack(convs))
            g_h.append(jnp.stack(hs))
        cache["g_ssm"] = (jnp.stack(g_conv).astype(cdt), jnp.stack(g_h))
        cache["shared_k"] = jnp.stack(sks)
        cache["shared_v"] = jnp.stack(svs)
        if "tail_blocks" in params:
            convs, hs = [], []
            for l in range(cfg.num_layers % cfg.hybrid_attn_every):
                lp = jax.tree_util.tree_map(lambda t: t[l],
                                            params["tail_blocks"])
                h = rms_norm(x, lp["ln1"], cfg.rms_eps)
                y, (cv, hh) = mamba_forward(lp, h, cfg, return_state=True)
                x = x + y
                convs.append(cv)
                hs.append(hh)
            cache["tail_ssm"] = (jnp.stack(convs).astype(cdt),
                                 jnp.stack(hs))
    elif cfg.ssm:
        def body(carry, lp):
            xx = carry
            h = rms_norm(xx, lp["ln1"], cfg.rms_eps)
            y, (conv, hh) = mamba_forward(lp, h, cfg, return_state=True)
            return xx + y, (conv.astype(cdt), hh)

        fn = jax.checkpoint(body,
                            policy=jax.checkpoint_policies.nothing_saveable)\
            if cfg.remat else body
        x, ssm = jax.lax.scan(fn, x, params["blocks"])
        cache["ssm"] = ssm
    else:
        pad = max_len - total

        def body(carry, lp):
            xx = carry
            h = rms_norm(xx, lp["ln1"], cfg.rms_eps)
            y, (k, v) = attention(lp, h, cfg, positions, return_kv=True)
            xx = xx + y
            h2 = rms_norm(xx, lp["ln2"], cfg.rms_eps)
            if cfg.moe:
                y2, _ = moe_layer(lp, h2, cfg)
                xx = xx + y2
            else:
                xx = xx + mlp(lp, h2, cfg)
            k = k.transpose(0, 2, 1, 3)
            v = v.transpose(0, 2, 1, 3)
            if pad:
                k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
            # sequence-parallel cache layout (matches decode's shardings;
            # without this the (L,B,KV,S,hd) output replicates S: ~26GB/dev
            # at 32k — §Perf H6)
            k = constrain(k.astype(cdt), "dp", None, "sp", None)
            v = constrain(v.astype(cdt), "dp", None, "sp", None)
            return xx, (k, v)

        fn = jax.checkpoint(body,
                            policy=jax.checkpoint_policies.nothing_saveable)\
            if cfg.remat else body
        x, (k, v) = jax.lax.scan(fn, x, params["blocks"])
        cache["k"], cache["v"] = k, v
    x_last = x[:, -1] if last_positions is None \
        else x[jnp.arange(x.shape[0]), last_positions]
    x_last = rms_norm(x_last, params["final_norm"], cfg.rms_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bd,dv->bv", x_last, head.astype(cdt),
                        preferred_element_type=jnp.float32)
    cache["pos"] = jnp.asarray(total, jnp.int32)
    return logits, cache
