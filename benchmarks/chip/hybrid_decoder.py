"""The hybrid decoder family (Nemotron-H): its sizes, the operations and
bytes its decode step needs, and what the program's configuration must say
to run it.

A configuration file names its family under ``family``; ``opcount.family``
finds this module by that name. The model is the one ``reference_hybrid.py``
computes: token embedding, then one mixer a layer as
``hybrid_override_pattern`` says — Mamba-2 ``M``, squared-ReLU MLP ``-``,
grouped-query attention ``*`` without position embedding — each ``x +=
mixer(RMSNorm(x))``, a final RMSNorm and an untied head. Counts come from
the file's keys, never from the program. ``Dims.L`` counts the ATTENTION
layers: what the dense family's readers multiply per-layer attention work
by (``paged_decode_roofline``); the Mamba and MLP layers have their own
counts. A token decoded at position ``p`` (``p`` tokens already cached)
attends over ``p + 1`` keys. ``decode_hbm_roofline`` reads a traced window
against the decode step's bandwidth floor these counts give.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import opcount

__all__ = ["Dims", "dims_of", "program_departures", "matmul_params",
           "attn_flops", "attn_bytes", "decode_flops", "decode_bytes",
           "weight_bytes", "decode_hbm_roofline"]

#: the decode-chunk program, by its jit name in the trace
DECODE_PROGRAM = "jit__decode_paged_impl"

#: what this family computes, as the file's keys state it
FIXED = {"mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
         "attention_bias": False, "mlp_bias": False, "use_bias": False,
         "mamba_proj_bias": False, "use_conv_bias": True,
         "tie_word_embeddings": False, "residual_in_fp32": False,
         "time_step_limit": [0, None]}


class Dims(NamedTuple):
    """The sizes the configuration file states (hashable: a static jit
    argument)."""
    L: int          # attention layers
    D: int          # d_model
    H: int          # query heads
    KV: int         # KV heads
    hd: int         # head size
    F: int          # MLP width
    V: int          # vocabulary
    Vp: int         # vocabulary padded to a multiple of 256
    eps: float      # RMSNorm epsilon
    pattern: str    # one character a layer: M, -, *
    Lm: int         # Mamba layers
    Lf: int         # MLP layers
    nh: int         # Mamba heads
    hp: int         # Mamba head size
    N: int          # state size
    G: int          # B/C groups
    K: int          # conv width

    @property
    def dI(self) -> int:
        return self.nh * self.hp

    @property
    def C(self) -> int:
        """Conv channels: x, then one B and one C per group."""
        return self.dI + 2 * self.G * self.N


def dims_of(model: Dict) -> Dims:
    """Read the sizes from a configuration file's model keys, refusing
    what this family does not hold."""
    bad = {k: model.get(k) for k, v in FIXED.items()
           if k in model and model[k] != v}
    if bad:
        raise ValueError(f"hybrid decoder: {bad} (holds {FIXED})")
    pattern = model["hybrid_override_pattern"]
    if len(pattern) != int(model["num_hidden_layers"]) \
            or set(pattern) - set("M-*"):
        raise ValueError(f"hybrid decoder: pattern {pattern!r} against "
                         f"{model['num_hidden_layers']} layers")
    D = int(model["hidden_size"])
    nh, hp = int(model["mamba_num_heads"]), int(model["mamba_head_dim"])
    if nh * hp != int(model["expand"]) * D:
        raise ValueError("hybrid decoder: mamba heads x head size must be "
                         "expand x hidden_size")
    V = int(model["vocab_size"])
    return Dims(pattern.count("*"), D, int(model["num_attention_heads"]),
                int(model["num_key_value_heads"]),
                int(model["attention_head_dim"]),
                int(model["intermediate_size"]), V, -(-V // 256) * 256,
                float(model["rms_norm_eps"]), pattern, pattern.count("M"),
                pattern.count("-"), nh, hp, int(model["ssm_state_size"]),
                int(model["n_groups"]), int(model["conv_kernel"]))


def program_departures(model: Dict, cfg) -> Dict:
    """Where the program's model configuration ``cfg`` departs from the
    file's keys or from this family: ``{what: (file, program)}``, empty
    when the program runs what the file states."""
    d = dims_of(model)
    want = {"hybrid_override_pattern": (d.pattern, cfg.layer_pattern),
            "num_hidden_layers": (len(d.pattern), cfg.num_layers),
            "hidden_size": (d.D, cfg.d_model),
            "num_attention_heads": (d.H, cfg.num_heads),
            "num_key_value_heads": (d.KV, cfg.num_kv_heads),
            "attention_head_dim": (d.hd, cfg.hd),
            "intermediate_size": (d.F, cfg.d_ff),
            "vocab_size": (d.V, cfg.vocab_size),
            "rms_norm_eps": (d.eps, cfg.rms_eps),
            "mamba_num_heads": (d.nh, cfg.ssm_heads),
            "mamba_head_dim": (d.hp, cfg.ssm_head_dim),
            "ssm_state_size": (d.N, cfg.ssm_state),
            "n_groups": (d.G, cfg.ssm_groups),
            "conv_kernel": (d.K, cfg.ssm_conv),
            "expand": (int(model["expand"]), cfg.ssm_expand),
            "tie_word_embeddings": (False, cfg.tie_embeddings),
            "mamba2": (2, cfg.ssm_version),
            "mlp": ("relu2", cfg.mlp_act),
            "pos_emb": ("none", cfg.pos_emb),
            "qk_norm": (False, cfg.qk_norm),
            "qkv_bias": (False, cfg.qkv_bias),
            "param_dtype": ("bfloat16", cfg.param_dtype)}
    return {k: v for k, v in want.items() if v[0] != v[1]}


def matmul_params(d: Dims) -> int:
    """Weights one token multiplies through: every layer's projections and
    the head (the embedding is a lookup)."""
    mamba = d.D * (d.dI + d.C + d.nh) + d.dI * d.D
    mlp = 2 * d.D * d.F
    attn = d.D * d.H * d.hd + 2 * d.D * d.KV * d.hd + d.H * d.hd * d.D
    return d.Lm * mamba + d.Lf * mlp + d.L * attn + d.D * d.Vp


def weight_bytes(d: Dims) -> int:
    """bf16 bytes of the weights a decode step reads once: the matrices it
    multiplies through and the conv filters."""
    return 2 * (matmul_params(d) + d.Lm * d.C * (d.K + 1))


def attn_flops(d: Dims, pos: int) -> int:
    """One attention layer's core for one token: scores and the weighted
    sum of values, a multiply and an add each, over ``pos + 1`` keys."""
    return 4 * d.H * d.hd * (pos + 1)


def attn_bytes(d: Dims, pos: int, block: int) -> int:
    """Bytes one attention layer's paged read must move for one token: the
    live pages of K and V in bf16 (a page is whole once touched), and the
    query in and the output out."""
    pages = pos // block + 1
    return pages * block * d.KV * d.hd * 2 * 2 + 2 * d.H * d.hd * 2


def _recurrence_flops(d: Dims) -> int:
    """One Mamba layer's state work for one token: ``h = a*h + dt*x*B``
    (3 a state element) and ``y = C.h`` (2), and the width-K conv."""
    return 5 * d.nh * d.hp * d.N + 2 * d.K * d.C


def decode_flops(d: Dims, pos: int) -> int:
    """Model FLOPs of decoding one token at ``pos``."""
    return (2 * matmul_params(d) + d.L * attn_flops(d, pos)
            + d.Lm * _recurrence_flops(d))


def decode_bytes(d: Dims, pos: int, block: int) -> int:
    """Bytes decoding one token at ``pos`` must move besides the weights:
    its row's Mamba state read and written in f32 and its conv window (the
    K-1 last conv inputs, bf16) read and written, in every Mamba layer, and
    its live KV pages in every attention layer."""
    state = d.nh * d.hp * d.N * 4 * 2 + (d.K - 1) * d.C * 2 * 2
    return d.Lm * state + d.L * attn_bytes(d, pos, block)


def decode_hbm_roofline(rec: Dict) -> Optional[float]:
    """The decode-chunk program's share of its bandwidth floor, in %, from
    a traced window's reduced records (as ``metrics/<name>.py`` readers
    take them): the weights once a step (each run of the program is
    ``decode_chunk`` steps; a run cut by the window's edge counts in part),
    and for each decoded token :func:`decode_bytes` at its position, over
    the peak HBM bandwidth, against the program's device time on the first
    chip. Notes its byte terms; None where the program ran no step or no
    token was decoded."""
    runs = [(t, share) for name, _, t, share in rec["modules"]
            if name == DECODE_PROGRAM]
    t = sum(d for d, _ in runs)
    steps = sum(share for _, share in runs) * rec["decode_chunk"]
    pos = opcount.decoded_positions(rec["requests"], 0.0, rec["window_s"])
    n = rec["counters"]["tokens_out"]
    if t <= 0 or not steps or not pos or n <= 0:
        return None
    d = dims_of(rec["model"])
    weights = steps * weight_bytes(d)
    per_token = n * sum(decode_bytes(d, p, rec["block_size"])
                        for p in pos) / len(pos)
    floor = (weights + per_token) / rec["peak"]["hbm_bytes_per_s"]
    rec.setdefault("notes", []).append(
        f"decode_hbm_roofline: {weights:.6g} weight bytes ({steps:.6g} "
        f"steps), {per_token:.6g} state, conv and KV bytes ({n} tokens); "
        f"floor {floor:.6g} s, {DECODE_PROGRAM} {t:.6g} s")
    return 100.0 * floor / t
