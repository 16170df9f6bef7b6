"""olmo-1b [dense] — non-parametric LayerNorm, tied embeddings.

Sources: OLMo, arXiv:2402.00838 (Table 1), and allenai/OLMo-1B
``config.json``. 16L d_model=2048 16H (kv=16, MHA) of 128, SwiGLU with a
hidden width of 8192 (``mlp_ratio`` 8, halved by the gate), LayerNorm with
no parameters (eps 1e-5), RoPE theta 10,000, no biases, tied embeddings,
no embedding scale, vocab 50,280 padded to 50,304, sequence 2048.
"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="olmo-1b", family="dense",
    n_layers=16, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=8192,
    vocab_size=50304, norm="nonparam", norm_eps=1e-5, tie_embeddings=True,
)
