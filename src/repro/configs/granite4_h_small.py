"""granite-4.0-h-small [hybrid]: 40L d_model=4096 vocab=100352 (tied),
36 Mamba-2 mixers (128 heads x 64, state 128, conv 4, one group) and 4
GQA attention mixers (32 query heads over 8 KV heads of 128, no
positional encoding) at layers 5, 15, 25 and 35; every layer an MoE of 72
experts of width 768, top-10 with a softmax over the chosen 10, beside
one shared expert of width 1536.  Embeddings times 12, each sub-block's
output times 0.22, attention scale 1/128, logits divided by 16.
[hf:ibm-granite/granite-4.0-h-small config.json]

Pattern period 10 (layer_types[0:10]: five Mamba layers, one attention
layer, four Mamba layers), repeated four times.  ``full()`` holds every
expert.  ``chip_share()`` is one chip's share of the deployment the
benchmark measures: eight chips share each layer by expert parallelism,
9 experts each (experts 0-8 here), with the mixers, router and shared
expert on every chip; one period of layers, the others lying on further
chips as pipeline stages.
"""

from repro.models.config import BlockDesc, ModelConfig

ARCH_ID = "granite-4.0-h-small"
SHARE_ID = "granite-4.0-h-small-ep8"
ATTENTION_AT = 5            # the attention layer's place in each period


def _pattern() -> tuple[BlockDesc, ...]:
    return tuple(BlockDesc(kind="attn" if i == ATTENTION_AT else "mamba",
                           moe=True) for i in range(10))


def full() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID,
        arch_kind="lm",
        n_layers=40,
        d_model=4096,
        n_heads=32,
        n_kv_heads=8,
        head_dim=128,
        d_ff=768,
        vocab_size=100352,
        block_pattern=_pattern(),
        use_rope=False,
        attn_scale=1 / 128,
        embedding_multiplier=12.0,
        residual_multiplier=0.22,
        logits_scaling=16.0,
        rms_eps=1e-5,
        tie_embeddings=True,
        n_experts=72,
        top_k=10,
        moe_d_ff=768,
        shared_d_ff=1536,
        experts_held=72,
        ssm_state=128,
        mamba_head_dim=64,
        mamba_expand=2,
        conv_kernel=4,
    )


def chip_share() -> ModelConfig:
    return full().replace(name=SHARE_ID, n_layers=10, experts_held=9)


def smoke() -> ModelConfig:
    return full().replace(
        n_layers=10, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
        d_ff=64, moe_d_ff=64, shared_d_ff=128, n_experts=16, top_k=8,
        experts_held=4, attn_scale=1 / 32, vocab_size=512, ssm_state=32,
        mamba_head_dim=32, logits_chunk=64, remat="none",
    )
