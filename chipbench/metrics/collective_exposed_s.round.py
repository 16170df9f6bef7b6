"""Seconds per round in which a collective op (all-gather, reduce-scatter,
all-reduce, collective-permute, an async collective's start or done) runs
on a device and no compute op does (the worst device): the FSDP
collectives' time the step does not hide. A loop's own event, which spans
its body, is neither."""
from chipbench import hostspans as hs
from chipbench import trace as tr


def read(ctx):
    t = ctx["trace"]
    if t.window() is None or not t.devices:
        return None
    lo, hi = t.window()
    worst = None
    for dev in t.devices:
        kinds = {"collective": [], "compute": [], "container": []}
        for name, s, e in t.ops[dev]:
            kinds[hs.op_kind(name)].append((s, e))
        coll, other = kinds["collective"], kinds["compute"]
        if not coll:
            continue
        alone = tr.intersect(tr.clip(tr.union(coll), lo, hi),
                             tr.gaps(tr.union(other), lo, hi))
        worst = max(worst or 0.0, tr.length(alone))
    if worst is None:
        return None
    return worst / 1e9 / len(t.rounds())
