"""Share of its roofline that local training reaches: the least time the
chip could take for the involved clients' steps, max(operations / bf16
peak, byte floor / HBM bandwidth) from ``counts/<model>.py``, over the
device-busy time inside ``backend.train_s`` spans (the worst device). At
this model's shapes the byte floor bounds it: memory-bound."""
from chipbench import trace as tr


def read(ctx):
    t = ctx["trace"]
    spans = tr.union(t.spans.get("backend.train_s", []))
    if t.window() is None or not t.devices or not spans:
        return None
    peaks = ctx["peaks"]
    least = 0.0
    for r in ctx["rounds"]:
        w = ctx["counts"].round_work(ctx["config"], ctx["traffic"], r)
        least += max(w["flops"] / peaks["bf16_flops_per_s"],
                     w["bytes"] / peaks["hbm_bytes_per_s"])
    busy = max(tr.length(tr.intersect(t.busy(d), spans)) for d in t.devices)
    return 100.0 * least / (busy / 1e9) if busy > 0 else None
