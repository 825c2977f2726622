"""qwen1.5-4b — dense MHA with QKV bias.  [hf:Qwen/Qwen1.5-4B; hf]"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="qwen1.5-4b", family="dense", n_layers=40, d_model=2560,
    n_heads=20, n_kv_heads=20, head_dim=128, d_ff=6912, vocab_size=151936,
    act="swiglu", qkv_bias=True, rope_theta=1e6,
    remat="dots_saveable")

SMOKE = CONFIG.replace(
    name="qwen1.5-smoke", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
    head_dim=16, d_ff=128, vocab_size=256, remat="none")
