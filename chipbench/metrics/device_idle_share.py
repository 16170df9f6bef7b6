"""Share of the traced window in which no operation ran on the device,
averaged over the devices: 1 - union of device-op intervals / window."""
from chipbench import trace as tr


def read(ctx):
    t = ctx["trace"]
    if t.window() is None or not t.devices:
        return None
    lo, hi = t.window()
    busy = [tr.length(tr.clip(t.busy(d), lo, hi)) for d in t.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
