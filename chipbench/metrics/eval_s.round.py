"""Seconds per round of the ``backend.eval_s`` span."""
from chipbench import trace as tr


def read(ctx):
    return tr.span_per_round(ctx["trace"], "backend.eval_s")
