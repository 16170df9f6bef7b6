"""Seconds per round of the ``backend.train_s`` span (local training, from
dispatch to ``block_until_ready``)."""
from chipbench import trace as tr


def read(ctx):
    return tr.span_per_round(ctx["trace"], "backend.train_s")
