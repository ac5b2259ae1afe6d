"""Qwen3-MoE-30B-A3B — MoE 128 experts top-8, QK-norm, untied head
[https://huggingface.co/Qwen/Qwen3-30B-A3B/blob/main/config.json;
Qwen3 Technical Report, arXiv:2505.09388]."""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-moe-30b-a3b", family="moe",
    n_layers=48, d_model=2048, n_heads=32, n_kv_heads=4,
    d_ff=768, vocab_size=151936, head_dim=128, qk_norm=True,
    rope_theta=1_000_000.0, norm_eps=1e-6, tie_embeddings=False,
    n_experts=128, experts_per_token=8, router_aux_coef=0.001,
)
