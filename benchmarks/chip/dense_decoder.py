"""The dense decoder family: its sizes, the operations and bytes its
decode step needs, and what the program's configuration must say to run
it.

A configuration file names its family under ``family``; ``opcount.family``
finds the module by that name, so a later family (experts, state-space
layers, other attention) adds a module of its own and edits none. This
one is the decoder that ``reference.py`` computes: token embedding, per
layer ``x += Attn(RMSNorm(x))`` and ``x += SwiGLU(RMSNorm(x))``, a final
RMSNorm and an untied head; grouped-query attention with rotary embedding
over the whole head and no biases. Counts come from the file's keys,
never from the program. A token decoded at position ``p`` (``p`` tokens
already cached) attends over ``p + 1`` keys.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

__all__ = ["Dims", "dims_of", "program_departures", "matmul_params",
           "attn_flops", "attn_bytes", "decode_flops"]

#: model keys a configuration file may leave out, at their usual values
MODEL_DEFAULTS = {"use_qkv_bias": False, "tie_word_embeddings": False}


class Dims(NamedTuple):
    """The sizes the configuration file states (hashable: a static jit
    argument)."""
    L: int          # layers
    D: int          # d_model
    H: int          # query heads
    KV: int         # KV heads
    hd: int         # head size
    F: int          # MLP width
    V: int          # vocabulary
    Vp: int         # vocabulary padded to a multiple of 256
    theta: float    # rotary base
    eps: float      # RMSNorm epsilon


def dims_of(model: Dict) -> Dims:
    """Read the sizes from a configuration file's model keys, refusing
    what this family does not hold."""
    if float(model.get("partial_rotary_factor", 1.0)) != 1.0:
        raise ValueError("dense decoder: full-width rotary only")
    if model.get("use_qkv_bias", False) or model.get("tie_word_embeddings",
                                                     False):
        raise ValueError("dense decoder: no q/k/v bias and an untied head "
                         "only")
    if model.get("hidden_act", "silu") != "silu":
        raise ValueError("dense decoder: SwiGLU (silu) only")
    V = int(model["vocab_size"])
    H = int(model["num_attention_heads"])
    D = int(model["hidden_size"])
    hd = int(model.get("head_dim") or D // H)
    return Dims(int(model["num_hidden_layers"]), D, H,
                int(model["num_key_value_heads"]), hd,
                int(model["intermediate_size"]), V, -(-V // 256) * 256,
                float(model["rope_theta"]), float(model["rms_norm_eps"]))


def program_departures(model: Dict, cfg) -> Dict:
    """Where the program's model configuration ``cfg`` departs from the
    file's keys or from this family: ``{what: (file, program)}``, empty
    when the program runs what the file states."""
    m = {**MODEL_DEFAULTS,
         "head_dim": model["hidden_size"] // model["num_attention_heads"],
         **model}
    want = {"num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.hd,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_eps,
            "use_qkv_bias": cfg.qkv_bias,
            "tie_word_embeddings": cfg.tie_embeddings}
    bad = {k: (m.get(k), v) for k, v in want.items() if m.get(k) != v}
    family = {"family": ("dense", cfg.family), "mlp_gated": (True,
                                                             cfg.mlp_gated),
              "qk_norm": (False, cfg.qk_norm), "pos_emb": ("rope",
                                                           cfg.pos_emb),
              "param_dtype": ("bfloat16", cfg.param_dtype)}
    bad.update({k: v for k, v in family.items() if v[0] != v[1]})
    return bad


def matmul_params(d: Dims) -> int:
    """Weights one token multiplies through: every layer's projections
    and MLP, and the head (the embedding is a lookup)."""
    per_layer = (d.D * d.H * d.hd + 2 * d.D * d.KV * d.hd + d.H * d.hd * d.D
                 + 3 * d.D * d.F)
    return d.L * per_layer + d.D * d.Vp


def attn_flops(d: Dims, pos: int) -> int:
    """One layer's attention core for one token: scores and the weighted
    sum of values, a multiply and an add each, over ``pos + 1`` keys."""
    return 4 * d.H * d.hd * (pos + 1)


def attn_bytes(d: Dims, pos: int, block: int) -> int:
    """Bytes one layer's paged attention must move for one token: the
    live pages of K and V in bf16 (a page is whole once touched), and the
    query in and the output out."""
    pages = pos // block + 1
    return pages * block * d.KV * d.hd * 2 * 2 + 2 * d.H * d.hd * 2


def decode_flops(d: Dims, pos: int) -> int:
    """Model FLOPs of decoding one token at ``pos``."""
    return 2 * matmul_params(d) + d.L * attn_flops(d, pos)
