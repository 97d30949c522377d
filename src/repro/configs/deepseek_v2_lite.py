"""DeepSeek-V2-Lite 16B-A2.4B — MLA without query compression (kv_lora
512), YaRN rope (factor 40), 64 routed experts top-6 + 2 shared, greedy
softmax routing without renormalised gates, first layer dense
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434].

Not modelled: the sequence-wise auxiliary loss (``seq_aux``; the program
adds its Switch loss), rotary on interleaved pairs (rotated here on
halves: a fixed permutation of the q_pe/k_pe columns)."""

from repro.models.config import (MLAConfig, ModelConfig, MoEConfig,
                                 YarnScaling)

YARN = YarnScaling(factor=40.0, original_max_position_embeddings=4096,
                   beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                   mscale_all_dim=0.707)


def full() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        vocab_size=102400, d_model=2048, n_layers=27,
        n_heads=16, n_kv_heads=16, d_ff=10944,
        block_pattern=("mla",) * 27,
        mla=MLAConfig(q_lora=0, kv_lora=512, qk_nope=128, qk_rope=64,
                      v_head=128),
        moe=MoEConfig(num_experts=64, top_k=6, d_expert=1408, num_shared=2,
                      first_dense_layers=1, dense_d_ff=10944,
                      norm_topk=False, routed_scale=1.0),
        mlp_act="silu", rope_theta=10000.0, rope_scaling=YARN,
    )


def smoke() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-smoke",
        vocab_size=512, d_model=128, n_layers=3,
        n_heads=4, n_kv_heads=4, d_ff=256,
        block_pattern=("mla",) * 3,
        mla=MLAConfig(q_lora=0, kv_lora=32, qk_nope=16, qk_rope=16, v_head=16),
        moe=MoEConfig(num_experts=8, top_k=2, d_expert=64, num_shared=2,
                      first_dense_layers=1, dense_d_ff=256,
                      norm_topk=False, dropless=True),
        mlp_act="silu", rope_scaling=YARN,
        param_dtype="float32", compute_dtype="float32",
        loss_chunk=64, remat=False,
    )
