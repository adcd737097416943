"""nemotron-h-47b [hybrid] — a per-layer pattern of Mamba-2 mixers (256
heads of 64, 8 B/C groups of state 256), squared-ReLU MLPs and GQA
attention without position embedding (64 query / 8 KV heads of 128), each
layer ``x += mixer(RMSNorm(x))``; 98 layers: 45 "M", 48 "-", 5 "*".
[hf:nvidia/Nemotron-H-47B-Base-8K]"""
from .base import ModelConfig

PATTERN = ("M-M-M-M-M-M-M-M-M*-M-M-M-M-M-M-M-M-M-M*-M-M-M-M-M*-M-M-M-M-M*-"
           "M-M-M-M-M-M-M---MM---M-M*-M-M-M-M-M-")

CONFIG = ModelConfig(
    name="nemotron-h-47b", family="hybrid",
    num_layers=len(PATTERN), d_model=8192, num_heads=64, num_kv_heads=8,
    d_ff=30720, vocab_size=131072, head_dim=128,
    pos_emb="none", mlp_act="relu2",
    ssm_version=2, ssm_state=256, ssm_conv=4, ssm_expand=2,
    ssm_head_dim=64, ssm_groups=8, ssm_chunk=128,
    layer_pattern=PATTERN, max_seq_len=8192,
)
