"""Seconds per round in which the device is idle and no backend span is
open: the driver, the transport and minibatch assembly (the worst device)."""
from chipbench import trace as tr


def read(ctx):
    t = ctx["trace"]
    if t.window() is None or not t.devices:
        return None
    lo, hi = t.window()
    outside = tr.gaps(tr.backend_spans(t), lo, hi)
    worst = max(tr.length(tr.intersect(tr.gaps(t.busy(d), lo, hi), outside))
                for d in t.devices)
    return worst / 1e9 / len(t.rounds())
