"""qwen3-14b [dense]: 40L d_model=5120 40H (GQA kv=8) head_dim=128
d_ff=17408 vocab=151936, per-head q/k RMSNorm, rms_norm_eps 1e-6 (the
``norm_eps`` default), untied head, max_position_embeddings 40960
[huggingface.co/Qwen/Qwen3-14B]."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-14b",
    family="dense",
    n_layers=40,
    d_model=5120,
    n_heads=40,
    n_kv_heads=8,
    head_dim=128,
    d_ff=17408,
    vocab_size=151936,
    qk_norm=True,
    rope_theta=1e6,
).validated()
