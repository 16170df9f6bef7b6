"""Seconds per round of the ``backend.train_step_s`` span: the gradient
regime's FSDP step, from dispatch to ``block_until_ready``
(``fl/backends.py:GradientBackend.run_round``)."""
from chipbench import hostspans as hs


def read(ctx):
    spans = hs.spans(ctx, __file__, "backend.train_step_s")
    if not spans:
        return None
    return hs.per_round(ctx, [(s, e) for s, e, _ in spans])
