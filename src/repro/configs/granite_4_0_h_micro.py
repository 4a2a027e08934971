"""granite-4.0-h-micro [mamba_hybrid]: 40L d_model=2048 — 36 Mamba-2 mixers
(64 heads of 64, state 128, one group, conv 4) and 4 NoPE GQA mixers (32
query heads, 8 KV heads of 64) at layer indices 5, 15, 25 and 35, a SwiGLU
MLP of 8192 after every mixer, tied vocab=100352, RMSNorm eps 1e-5, and
the published multipliers: embeddings x12, every sublayer's output x0.22
before its residual add, softmax scale 1/64, logits / 8.
[hf:ibm-granite/granite-4.0-h-micro config.json]"""
from repro.models.common import ArchConfig

ARCH_ID = "granite-4.0-h-micro"

#: one period of ``layer_types``: attention at index 5 of every 10
PATTERN = ("mamba",) * 5 + ("attn",) + ("mamba",) * 4


def full() -> ArchConfig:
    return ArchConfig(
        name=ARCH_ID, family="mamba_hybrid", layer_pattern=PATTERN,
        n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
        d_ff=8192, vocab_size=100352, rope_pct=0.0,       # NoPE
        ssm_state=128, ssm_expand=2, ssm_head_dim=64, ssm_chunk=256,
        conv_width=4, mlp="swiglu", norm="rmsnorm", norm_eps=1e-5,
        tie_embeddings=True, embedding_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.015625,
        logits_scaling=8.0,
        # chunked prefill attention from 2048 tokens, queries and keys in
        # blocks of 1024: no prompt materialises a whole score matrix
        attn_chunk_min_seq=2048, attn_q_chunk=1024, attn_chunk=1024,
    )


def reduced() -> ArchConfig:
    return full().with_(dtype="float32", n_layers=10, d_model=64, n_heads=4,
                        n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=512,
                        ssm_state=16, ssm_head_dim=16, ssm_chunk=8)
