"""Model operations of OLMo-1B's federated gradient rounds, counted from
shapes.

Multiply-adds count twice. A token's forward pass is 2 x the parameters
(the tied table counted once, as the unembedding: the embedding lookup is
a gather), its backward pass 4 x; causal attention adds 2 x L x S^2 x d a
sequence forward (QK^T and AV over the S^2 / 2 pairs a causal mask
keeps), 3 x that with the backward. Rows whose weight is 0 (clients the
PON did not involve) and remat's recomputed forward pass are left out.
"""
from __future__ import annotations


def params(widths: dict) -> int:
    d, L = widths["d_model"], widths["n_layers"]
    ff = widths["mlp_ratio"] * d // 2
    return widths["embedding_size"] * d + L * (4 * d * d + 3 * d * ff)


def train_flops(widths: dict, seq: int, causal: bool = True) -> float:
    """One sequence's forward and backward operations; ``causal=False``
    counts attention over every pair, as a masked program computes it."""
    attn = 6 * widths["n_layers"] * seq * seq * widths["d_model"]
    return float(6 * params(widths) * seq + (attn if causal else 2 * attn))


def round_work(config: dict, traffic: dict, rec: dict) -> dict:
    """Model operations of one round's step over its weighted rows."""
    rows = int(rec["involved"])
    return {"flops": rows * train_flops(config["widths"], traffic["seq"])}
