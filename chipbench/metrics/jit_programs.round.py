"""Programs JAX obtained inside the traced window per round: compiles plus
persistent-cache reads. A steady state that reuses its programs reads 0."""
from chipbench import progtrace as pt


def read(ctx):
    p = pt.program(ctx, __file__)
    t = ctx["trace"]
    if p is None or not (p.instrumented or p.calls):
        return None
    lo, hi = t.window()
    ends = [e for stage in ("jax.compile", "jax.cache_read")
            for _, e in p.jax(stage)]
    return sum(lo <= e <= hi for e in ends) / len(t.rounds())
