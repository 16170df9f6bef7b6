"""Seconds per round in the program's ``round.transport`` span: selection,
failures and the PON simulation (``fl/loop.py:sync_round``)."""
from chipbench import progtrace as pt


def read(ctx):
    p = pt.program(ctx, __file__)
    if p is None:
        return None
    spans = (p.intervals("round.transport") if p.instrumented
             else p.calls.get("loop.py:_transport_stage", []))
    return pt.per_round(ctx["trace"], spans)
