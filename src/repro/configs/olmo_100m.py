"""~100M-parameter olmo-family model for the end-to-end training example
(examples/train_lm.py) — small enough to train a few hundred steps on CPU,
big enough to be a real LM."""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="olmo-100m", family="dense",
    n_layers=8, d_model=512, n_heads=8, n_kv_heads=8, head_dim=64,
    d_ff=2048, vocab_size=50304, norm="nonparam", norm_eps=1e-5,
    tie_embeddings=True,
    q_chunk=128, loss_chunks=1,
)
