"""qwen3-0.6b [dense]: qk_norm, GQA, tied embeddings.

Source: Qwen/Qwen3-0.6B ``config.json`` (huggingface.co/Qwen/Qwen3-0.6B):
28 layers, hidden 1024, 16 query heads, 8 KV heads, ``head_dim=128`` (so
the attention width 16 x 128 = 2048 is twice the hidden size), intermediate
3072, vocab 151936, ``tie_word_embeddings=true``, ``rope_theta=1e6``, RMSNorm
with per-head q/k norms.  Full attention => long_500k skipped.

``SMOKE`` is the same family cut for the CPU tests: it sets its own
``head_dim`` (d_model / n_heads) and keeps the tied head.
"""
import dataclasses
from .base import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-0.6b",
    family="dense",
    n_layers=28,
    d_model=1024,
    n_heads=16,
    n_kv_heads=8,
    d_ff=3072,
    vocab_size=151936,
    head_dim=128,
    qk_norm=True,
    rope_theta=1000000.0,
    tie_embeddings=True,
    long_context_ok=False,
)

SMOKE = dataclasses.replace(
    CONFIG, n_layers=3, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
    vocab_size=256, head_dim=16,
)
