"""deepseek-v2-lite [deepseek] — 27L (the first dense, SwiGLU 10944),
d_model=2048, 16 heads of multi-head latent attention (kv_lora_rank 512,
q/k 128 nope + 64 rope, v 128, no query compression, YaRN x40 over 4096),
DeepSeekMoE 64 routed experts of width 1408, top-6 softmax gates not
renormalised, 2 shared experts, vocab=102400
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]."""
from .base import ModelConfig, Yarn

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="deepseek",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab=102400, rope_theta=10_000.0,
    kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    yarn=Yarn(factor=40.0, original_len=4096, beta_fast=32.0, beta_slow=1.0,
              mscale=0.707, mscale_all_dim=0.707),
    n_experts=64, top_k=6, moe_d_ff=1408, n_shared_experts=2,
    n_dense_layers=1, aux_loss_alpha=0.001,
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json",
)
