"""qwen2-72b — Qwen2-72B as published (https://huggingface.co/Qwen/Qwen2-72B).

head_dim is derived (8192 / 64 = 128); the lm_head is untied.
"""
from repro.models.common import ModelConfig
import dataclasses

CONFIG = ModelConfig(
    name="qwen2-72b", family="dense",
    n_layers=80, d_model=8192, n_heads=64, n_kv_heads=8,
    d_ff=29568, vocab=152064,
    qkv_bias=True, rope_theta=1e6,
)


def reduced() -> ModelConfig:
    """Tiny same-family config for CPU smoke tests."""
    return _reduce(CONFIG)


from repro.configs._reduce import _reduce  # noqa: E402
