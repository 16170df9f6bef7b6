"""Device-idle seconds per round inside ``backend.train_s`` (the worst
device): local training's time with no operation running on the chip."""
from chipbench import trace as tr


def read(ctx):
    t = ctx["trace"]
    spans = tr.union(t.spans.get("backend.train_s", []))
    if t.window() is None or not t.devices or not spans:
        return None
    lo, hi = t.window()
    worst = max(tr.length(tr.intersect(tr.gaps(t.busy(d), lo, hi),
                                       tr.clip(spans, lo, hi)))
                for d in t.devices)
    return worst / 1e9 / len(t.rounds())
