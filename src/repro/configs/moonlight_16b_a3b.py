"""moonlight-16b-a3b — the DeepSeek-V3 block at 2048 wide: one leading
dense layer, then 26 MoE layers; multi-head latent attention without a
query LoRA (latent 512, key 128 + a shared rotary 64, value 128, 16
heads); 64 routed experts of 1408, top 6 by sigmoid scores with a
selection-only bias (aux-free balancing), the 6 weights renormalized and
scaled by 2.446, plus 2 shared experts.
[hf:moonshotai/Moonlight-16B-A3B config.json]

Hugging Face's DeepSeek-V3 code de-interleaves the 64 rotary dims
before rotating them; this program rotates first half against second
half, which is the same model with a fixed permutation of the rotary
columns of ``wq`` and ``wkva``."""
import dataclasses

from repro.models.config import LayerSpec, ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    arch_type="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,              # qk_nope_head_dim + qk_rope_head_dim
    d_ff=11264,                # the leading dense layer's MLP
    vocab_size=163840,
    rope_theta=50_000.0,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    leading=(LayerSpec("mla", "dense"),),       # first_k_dense_replace 1
    block_pattern=(LayerSpec("mla", "moe"),),
    num_blocks=26,
    num_experts=64,
    num_experts_per_tok=6,
    num_shared_experts=2,
    moe_d_ff=1408,
    router_scoring="sigmoid",                   # norm_topk_prob true
    routed_scaling_factor=2.446,
    norm_eps=1e-5,
    citation="[hf:moonshotai/Moonlight-16B-A3B]",
)

SMOKE = dataclasses.replace(
    CONFIG, num_layers=2, num_blocks=1, d_model=256, num_heads=4,
    num_kv_heads=4, head_dim=48, d_ff=512, vocab_size=512,
    kv_lora_rank=64, qk_nope_head_dim=32, qk_rope_head_dim=16,
    v_head_dim=32, num_experts=4, num_experts_per_tok=2,
    num_shared_experts=1, moe_d_ff=128)
