"""deepseek-v2-lite-16b [moe]: 27L d_model=2048 16H (MLA kv_lora=512)
d_ff_expert=1408 vocab=102400, MoE 64 routed top-6 + 2 shared
[arXiv:2405.04434; hf].

Assignment note (DESIGN.md §4): the assignment header reads "64e top-6" with
"2 shared+160 routed" in the notes; the public V2-Lite checkpoint has 64
routed experts (160 belongs to full V2), so we implement 64 and expose
n_experts for the 160 variant.  Layer 0 uses a dense SwiGLU FFN (10944) as
in the checkpoint; layers 1..26 are MoE.  As published: YaRN rope_scaling
(factor 40 over 4096 positions), a router that takes the top 6 of the
softmax over all 64 experts without renormalising, and a latent cache in
the model dtype.
"""
from ..models.config import ArchConfig, LayerSpec, YaRN

CONFIG = ArchConfig(
    name="deepseek-v2-lite-16b", n_layers=27, d_model=2048, n_heads=16,
    n_kv_heads=16, d_ff=10944, vocab=102400,
    mla=True, kv_lora=512, rope_head_dim=64, nope_head_dim=128,
    v_head_dim=128,
    n_experts=64, top_k=6, n_shared_experts=2, d_ff_expert=1408,
    prelude=(LayerSpec("attn", "swiglu"),),
    pattern=(LayerSpec("attn", "moe"),), rope_theta=1.0e4,
    rope_scaling=YaRN(factor=40.0, original_max_position_embeddings=4096,
                      beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                      mscale_all_dim=0.707),
    moe_impl="grouped", norm_topk_prob=False, kv_cache_dtype="model",
)

SMOKE = CONFIG.scaled(n_layers=3, d_model=128, n_heads=4, n_kv_heads=4,
                      d_ff=256, vocab=512, kv_lora=32, rope_head_dim=16,
                      nope_head_dim=32, v_head_dim=32, n_experts=4, top_k=2,
                      n_shared_experts=1, d_ff_expert=64, remat="none")
