"""Granite-3.1 8B [hf:ibm-granite/granite-3.1-8b-instruct]: dense GQA,
tied head, 128k positions (rope_theta 1e7), and the published scaling
multipliers on the embedding, attention scores, residual branches and
logits.  The registry keeps the name ``granite-3-8b``: 3.1 is the same
network as 3.0 trained to a longer context."""
from repro.common.config import ModelConfig

CONFIG = ModelConfig(
    name="granite-3-8b", family="dense",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8, d_head=128,
    d_ff=12800, vocab=49155, act="swiglu", rope_theta=1e7, norm_eps=1e-5,
    tie_embeddings=True, embedding_multiplier=12.0,
    attention_multiplier=0.0078125, residual_multiplier=0.22,
    logits_scaling=16.0,
)
