"""zamba2-1.2b [hybrid] — Mamba2 backbone + shared attention block
[arXiv:2411.15242; https://huggingface.co/Zyphra/Zamba2-1.2B].

38 Mamba2 layers, d_model=2048, 64 SSD heads x 64, d_state=64, one B/C
group, vocab 32000, tied embedding, RMSNorm eps 1e-5.  ONE weight-shared
transformer block is invoked before the Mamba mixer of layers 6, 12, ...,
36 (6 invocations).  It reads ``[h; e]`` (the hidden state concatenated
with the embedding output, 4096 wide): RMSNorm, attention with 32 heads x
128 over that input, rotary (rotate-half, theta 1e4), softmax scale
``(128 / 2) ** -0.5``, output back to 2048; then RMSNorm and a gated GELU
(exact erf) MLP of width 8192.  Each invocation adds its own rank-128
adapters to q, k, v and to the MLP's gate/up projection, and its own
2048 x 2048 linear, whose output is added to that layer's Mamba input:
``h' = h + Mamba(norm(h + linear(block(h, e))))``.  Runs long_500k.
"""
from .base import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="zamba2-1.2b",
    family="hybrid",
    n_layers=38,
    d_model=2048,
    n_heads=32,
    n_kv_heads=32,
    d_head=128,
    d_ff=8192,
    vocab_size=32_000,
    mlp_kind="geglu_erf",
    norm_kind="rmsnorm",
    norm_eps=1e-5,
    rope_style="half",
    attn_scale=(128 / 2) ** -0.5,
    tie_embeddings=True,
    ssm=SSMConfig(d_state=64, d_conv=4, expand=2, head_dim=64, chunk=256),
    shared_attn_every=6,
    attn_in=4096,
    adapter_rank=128,
    attn_adapters=True,
    source="arXiv:2411.15242; https://huggingface.co/Zyphra/Zamba2-1.2B",
)
