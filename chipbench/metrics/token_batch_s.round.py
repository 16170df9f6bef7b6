"""Seconds per round in the gradient regime's ``round.minibatch`` span:
the selected clients' token rows drawn on the host and put on the devices
(``fl/backends.py:GradientBackend.run_round``)."""
from chipbench import hostspans as hs


def read(ctx):
    spans = hs.spans(ctx, __file__, "round.minibatch")
    if not spans:
        return None
    return hs.per_round(ctx, [(s, e) for s, e, _ in spans])
