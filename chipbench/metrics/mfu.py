"""The rounds' model operations (``counts/<model>.py``) over the traced
window's seconds times the chips times the chip's bf16 peak."""


def read(ctx):
    t = ctx["trace"]
    if t.window() is None or not t.devices:
        return None
    lo, hi = t.window()
    flops = sum(ctx["counts"].round_work(ctx["config"], ctx["traffic"], r)
                ["flops"] for r in ctx["rounds"])
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / ((hi - lo) / 1e9 * ctx["chips"] * peak)
