"""moonlight-16b-a3b [moe] — 27L d=2048 16H d_ff=11264 vocab=163840.

DeepSeek-V3's block (``model_type`` deepseek_v3) at Moonlight's sizes:
MLA with no query low rank (kv_lora 512, nope 128, rope 64, v_head 128);
the first layer dense, the other 26 MoE with 2 shared + 64 routed
experts of width 1408, top-6 by sigmoid scores with a correction bias,
weights normalised and scaled by 2.446, the sequence-wise balance loss.
Every expert is held (dropless); a chip's share under expert parallelism
sets ``experts_held``. [huggingface.co/moonshotai/Moonlight-16B-A3B,
arXiv:2502.16982]
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=11264, vocab_size=163_840,
    segments=((("mla:dense",), 1), (("mla:moe",), 26)),
    norm_eps=1e-5, rope_theta=50_000.0,
    q_lora_rank=0, kv_lora_rank=512,
    qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, head_dim=192,
    n_experts=64, top_k=6, moe_d_ff=1408, n_shared_experts=2,
    router_aux_coef=1e-4, router_score="sigmoid", routed_scale=2.446,
    experts_held=64,
    citation="huggingface.co/moonshotai/Moonlight-16B-A3B (arXiv:2502.16982)",
)


def smoke_config():
    return ModelConfig(
        name="moonlight-smoke", family="moe",
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
        d_ff=96, vocab_size=128,
        segments=((("mla:dense",), 1), (("mla:moe",), 1)),
        q_lora_rank=0, kv_lora_rank=32,
        qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, head_dim=24,
        n_experts=4, top_k=2, moe_d_ff=24, n_shared_experts=2,
        router_aux_coef=1e-4, router_score="sigmoid", routed_scale=2.446,
        experts_held=2,
        citation="huggingface.co/moonshotai/Moonlight-16B-A3B (reduced)",
    )
