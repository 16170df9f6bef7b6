"""Seconds per round in the program's ``round.minibatch`` span: the padded
clients' minibatch draws on the host and their stacking onto the device
(``fl/backends.py:ClientStackedBackend.run_round``)."""
from chipbench import progtrace as pt


def read(ctx):
    p = pt.program(ctx, __file__)
    if p is None:
        return None
    spans = (p.intervals("round.minibatch") if p.instrumented
             else pt.before_train(ctx["trace"],
                                  p.calls.get("backends.py:run_round", [])))
    return pt.per_round(ctx["trace"], spans)
