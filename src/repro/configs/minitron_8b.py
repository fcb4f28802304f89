"""minitron-8b [dense] — width-pruned Nemotron-4 15B [arXiv:2407.14679;
hf nvidia/Minitron-8B-Base, NemotronForCausalLM].

32L d_model=4096 48H×128 (GQA kv=8; q width 6144) d_ff=16384 vocab=256000.
Nemotron block: LayerNorm1p (eps 1e-5), RoPE on half of each head
(θ 10000), non-gated squared-ReLU MLP, untied output head (``lm_head``).
"""
from repro.models.common import ModelConfig
from repro.models.registry import register

CONFIG = register(ModelConfig(
    name="minitron-8b",
    family="lm",
    n_layers=32,
    d_model=4096,
    n_heads=48,
    n_kv_heads=8,
    head_dim=128,
    d_ff=16384,
    vocab=256000,
    rope_theta=1e4,
    rotary_pct=0.5,
    mlp="relu2",
    norm="layernorm1p",
    norm_eps=1e-5,
    tie_embeddings=False,
))
