"""Host seconds per round inside the traced window in JAX's compile
pipeline: tracing to a jaxpr, lowering, and compiling or reading the
persistent cache (the union of those intervals)."""
from chipbench import progtrace as pt


def read(ctx):
    p = pt.program(ctx, __file__)
    if p is None or not (p.instrumented or p.calls):
        return None
    spans = [iv for stage in pt.JAX_STAGES for iv in p.jax(stage)]
    return pt.per_round(ctx["trace"], spans) or 0.0
