"""JAX profiler annotations + wall-time capture for the compute side.

Two complementary mechanisms:

  * :func:`annotate` — a ``jax.profiler.TraceAnnotation`` context (named
    interval in a captured XLA profile) for *host-side* regions: backend
    train/eval calls, kernel dispatch in the benchmarks.
  * :func:`named_scope` — ``jax.named_scope`` for *traced* code: inside a
    ``jit`` the annotation attaches to the emitted HLO ops, which is how
    ``agg_reduce``/``quantize`` show up as named regions in device
    profiles (``repro.kernels.ops`` wraps every public kernel).

:func:`timed` measures one callable's wall time — blocking on JAX arrays
so compile + dispatch + execute are all inside the measurement — and
feeds both a registry histogram and (optionally) a tracer wall span. The
first call through a jitted function is its compile; callers that want
compile vs steady-state split simply time the first call separately
(``benchmarks/bench_kernels.py`` does).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Tuple

import jax


def annotate(name: str):
    """Host-side profiler annotation."""
    return jax.profiler.TraceAnnotation(name)


def named_scope(name: str):
    """Trace-time scope: names the HLO emitted under it."""
    return jax.named_scope(name)


def timed(name: str, fn: Callable, *args,
          metrics=None, tracer=None, **kwargs) -> Tuple[Any, float]:
    """Run ``fn(*args, **kwargs)`` under a profiler annotation, blocking on
    the result; returns ``(result, wall_seconds)`` and records the timing
    into ``metrics.histogram(name)`` / a tracer wall span when given.

    A failed device computation (an out-of-memory, a refused kernel)
    surfaces in the block and propagates: no timing is recorded for it."""
    t0 = time.perf_counter()
    with annotate(name):
        out = jax.block_until_ready(fn(*args, **kwargs))
    dt = time.perf_counter() - t0
    if metrics is not None:
        metrics.histogram(name).observe(dt)
    if tracer is not None and tracer.enabled:
        # _wall_now pre-subtracts offset_s exactly because add_span re-adds
        # it — wall lanes always land at true host time
        now = tracer._wall_now()
        tracer.add_span(name, now - dt, now, lane=("wall", "compute"),
                        cat="profile")
    return out, dt
