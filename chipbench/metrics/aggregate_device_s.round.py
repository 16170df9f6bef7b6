"""Device-busy seconds per round outside the train and eval spans: the
aggregate and server update, with the minibatch upload (the worst
device)."""
from chipbench import trace as tr


def read(ctx):
    t = ctx["trace"]
    if t.window() is None or not t.devices:
        return None
    lo, hi = t.window()
    spans = tr.union(iv for n in ("backend.train_s", "backend.eval_s")
                     for iv in t.spans.get(n, []))
    if not spans:
        return None
    outside = tr.gaps(spans, lo, hi)
    worst = max(tr.length(tr.intersect(tr.clip(t.busy(d), lo, hi), outside))
                for d in t.devices)
    return worst / 1e9 / len(t.rounds())
