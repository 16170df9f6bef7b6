"""recurrentgemma-9b [hybrid] — RG-LRU + local attention, 1:2 pattern.

[arXiv:2402.19427; unverified] 38L d_model=4096 16H (MQA kv=1) d_ff=12288
vocab=256000. Unit = (rglru, rglru, attn) x 12 + tail (rglru, rglru);
attention layers use a 2048-token sliding window -> O(1) decode state,
so long_500k applies.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="recurrentgemma-9b", family="hybrid",
    n_layers=38, d_model=4096, n_heads=16, n_kv_heads=1, d_ff=12288,
    vocab_size=256000, head_dim=256, window=2048,
    block_pattern=("rglru", "rglru", "attn"), embed_scale=True,
)
