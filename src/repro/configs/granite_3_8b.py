"""granite-3-8b — dense GQA decoder (IBM ``GraniteForCausalLM``).

[hf:ibm-granite/granite-3.0-8b-base]: 40L d_model=4096 32H (GQA kv=8,
head_dim 128) d_ff=12800 vocab=49155, SwiGLU, RMSNorm eps 1e-5, rope_theta
10000, tied embeddings, bf16.  Its four scalar multipliers change the
residual and attention paths:

- ``embedding_multiplier`` 12: ``h0 = embed[ids] * 12``;
- ``attention_multiplier`` 1/128: the softmax scale, in place of
  1/sqrt(head_dim);
- ``residual_multiplier`` 0.22 on both branches of every layer:
  ``h = h + 0.22 * attn(norm(h))``, then ``h = h + 0.22 * mlp(norm(h))``;
- ``logits_scaling`` 16: ``logits = (norm(h) @ embed.T) / 16``.
"""
from .base import ModelConfig, ParallelConfig

CONFIG = ModelConfig(
    arch_id="granite-3-8b",
    family="dense",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=12800,
    vocab_size=49155,
    rope_theta=10_000.0,
    tie_embeddings=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    attention_multiplier=0.0078125,
    logits_scaling=16.0,
    parallel=ParallelConfig(train_dp_only=True),
    source="[hf:ibm-granite/granite-3.0-8b-base]",
)
