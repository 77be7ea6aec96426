"""DeepSeek-V2-Lite 16B — MLA + fine-grained MoE [arXiv:2405.04434].

Published widths (hf:deepseek-ai/DeepSeek-V2-Lite `config.json`): 27L,
d_model=2048, 16 heads with a direct q projection (`q_lora_rank` null),
MLA kv_lora=512 with nope/rope/v head dims 128/64/128, 64 routed
experts of width 1408 (top-6, softmax scores, `norm_topk_prob` false,
`routed_scaling_factor` 1) plus 2 shared (2 x 1408), a leading dense
FFN of 10944, vocab 102400 untied. The same `Model` code as
`deepseek-v2-236b`.

Not registered: the registry (`list_archs`) holds the ten architectures
of the dry-run grid. `expert_share` states what one chip of an
expert-parallel deployment holds of it.
"""
import dataclasses

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,                 # MLA: logical heads (cache is latent)
    head_dim=128,
    d_ff=10944,                    # dense FFN width (layer 0)
    vocab_size=102400,
    mlp_variant="swiglu",
    tie_embeddings=False,
    rope_theta=10000.0,
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0,
                  d_head_nope=128, d_head_rope=64, d_head_v=128),
    moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                  num_shared_experts=2, d_ff_shared=1408,
                  norm_topk_prob=False,
                  interval=1, offset=1),   # layer 0 dense, rest MoE
)


def expert_share(cfg: ModelConfig, *, ways: int, rank: int = 0,
                 n_layers: int, vocab_rows: int) -> ModelConfig:
    """What chip `rank` of `ways`-way expert parallelism holds of `cfg`:
    its contiguous range of each layer's routed experts (the router
    keeps every output), the first `n_layers` layers (the rest are
    further pipeline stages) and `vocab_rows` rows of the embedding and
    the head (the vocabulary slice). Attention, the shared experts and
    the leading dense layer stay whole."""
    m = cfg.moe
    if m.num_experts % ways:
        raise ValueError(f"{m.num_experts} experts do not split {ways} "
                         "ways")
    held = m.num_experts // ways
    return cfg.replace(
        name=f"{cfg.name}-{n_layers}L-ep{ways}",
        n_layers=n_layers, vocab_size=vocab_rows,
        moe=dataclasses.replace(m, expert_start=rank * held,
                                experts_held=held))


EP8_SHARE = expert_share(CONFIG, ways=8, n_layers=5, vocab_rows=12800)
