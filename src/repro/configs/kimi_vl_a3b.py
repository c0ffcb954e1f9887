"""kimi-vl-a3b [vlm]: Kimi-VL-A3B-Instruct's language model
[huggingface.co/moonshotai/Kimi-VL-A3B-Instruct config.json].

27L d_model=2048 16H vocab=163840, untied head, no embedding scale. MLA in
every layer: kv_lora_rank=512, no q_lora, qk_nope 128, qk_rope 64,
v_head_dim 128, rope_theta 800,000. Layer 0 dense (SwiGLU 11,264); layers
1-26 MoE: 64 routed experts of 1,408 + 2 shared, top-6, sigmoid scores with
a selection bias (``noaux_tc``, one group), normalised, scaled 2.446. The
vision tower (MoonViT) is not built: the P2M sensor and a projector
(``models/projector.py``) give the image embeddings.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="kimi-vl-a3b",
    family="vlm",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,
    vocab_size=163840,
    head_dim=128,
    block_pattern=("mla",),
    kv_lora_rank=512,
    q_lora_rank=0,
    rope_head_dim=64,
    num_experts=64,
    num_shared_experts=2,
    top_k=6,
    first_dense_layers=1,
    dense_d_ff=11264,
    moe_routing="sigmoid",
    routed_scaling=2.446,
    rope_theta=800000.0,
    norm_eps=1e-5,
    scale_embeddings=False,
    source="https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/blob/main/"
           "config.json",
)
