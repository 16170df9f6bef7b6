"""Program spans on the profiler's clock, and JAX's compile pipeline.

  * :func:`span` — a ``jax.profiler.TraceAnnotation`` for a host-side
    region of the round (``round.transport``, ``round.minibatch``,
    ``train.chunk``, ``round.aggregate``, the backend calls). Its keyword
    stats land as event stats in a captured ``.xplane.pb``; when the
    ambient ``repro.obs`` tracer is enabled the same interval also goes
    onto the tracer's ``wall / compute`` lane.
  * :func:`named_scope` — ``jax.named_scope`` for *traced* code: inside a
    ``jit`` the annotation attaches to the emitted HLO ops, which is how
    ``agg_reduce``/``quantize`` show up as named regions in device
    profiles (``repro.kernels.ops`` wraps every public kernel).
  * :func:`timed` — one callable's wall time inside a :func:`span`,
    blocking on JAX arrays so dispatch and execution are both inside it.
  * :func:`watch_jax` — one process-wide ``jax.monitoring`` listener
    (installed on import): JAX's traces, lowerings, compiles and
    persistent-cache reads are counted, with their seconds, into the
    ambient registry as ``jax.*``, and each lands in a captured trace as
    a marker ``jax.<stage>`` whose ``dur_ns`` stat gives the interval
    that ends at the marker (``chipbench/progtrace.py`` reads them).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
from jax.profiler import TraceAnnotation

from repro.obs.context import get as _obs_get

_WALL_LANE = ("wall", "compute")


class _WallSpan:
    """A TraceAnnotation that also records its interval on a tracer."""

    __slots__ = ("_name", "_stats", "_tracer", "_ann", "_t0")

    def __init__(self, name: str, stats: Dict[str, Any], tracer):
        self._name, self._stats, self._tracer = name, stats, tracer
        self._ann = TraceAnnotation(name, **stats)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set_metadata(self, **stats):
        """Stats known only inside the span (as TraceAnnotation's)."""
        self._ann.set_metadata(**stats)
        self._stats = {**self._stats, **stats}

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        # _wall_now pre-subtracts offset_s exactly because add_span re-adds
        # it — wall lanes always land at true host time
        now = self._tracer._wall_now()
        self._tracer.add_span(self._name, now - dt, now, lane=_WALL_LANE,
                              cat="profile", args=self._stats or None)
        return False


def span(name: str, **stats):
    """A host-side program span: ``with profile.span("train.chunk",
    chunk=0, rows=16) as sp: ...``. Stats are ints, floats or strings;
    ``sp.set_metadata(**stats)`` adds those known only inside it."""
    tracer = _obs_get().tracer
    if not tracer.enabled:
        return TraceAnnotation(name, **stats)
    return _WallSpan(name, stats, tracer)


def named_scope(name: str):
    """Trace-time scope: names the HLO emitted under it."""
    return jax.named_scope(name)


def timed(name: str, fn: Callable, *args,
          metrics=None, span_stats: Optional[Dict[str, Any]] = None,
          **kwargs) -> Tuple[Any, float]:
    """Run ``fn(*args, **kwargs)`` inside ``span(name, **span_stats)``,
    blocking on the result; returns ``(result, wall_seconds)`` and records
    the timing into ``metrics.histogram(name)`` when given.

    A failed device computation (an out-of-memory, a refused kernel)
    surfaces in the block and propagates: no timing is recorded for it."""
    t0 = time.perf_counter()
    with span(name, **(span_stats or {})):
        out = jax.block_until_ready(fn(*args, **kwargs))
    dt = time.perf_counter() - t0
    if metrics is not None:
        metrics.histogram(name).observe(dt)
    return out, dt


# --- JAX's compile pipeline ------------------------------------------------

# monitoring event -> stage; the backend-compile interval also covers a
# persistent-cache read, told apart by the cache-hit event inside it
_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           "/jax/core/compile/backend_compile_duration": "compile"}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# registry counter names per stage: (count, seconds)
COUNTERS = {"trace": ("jax.traces", "jax.trace_s"),
            "lower": ("jax.lowerings", "jax.lower_s"),
            "compile": ("jax.compiles", "jax.compile_s"),
            "cache_read": ("jax.cache_reads", "jax.cache_read_s")}

_watch = {"installed": False}
_hit = threading.local()


def _on_event(event: str, **_):
    if event == _CACHE_HIT:
        _hit.pending = True


def _on_duration(event: str, secs: float, fun_name: str = "", **_):
    stage = _STAGES.get(event)
    if stage is None:
        return
    if stage == "compile" and getattr(_hit, "pending", False):
        stage, _hit.pending = "cache_read", False
    obs = _obs_get()
    n, s = COUNTERS[stage]
    obs.metrics.counter(n).add(1)
    obs.metrics.counter(s).add(secs)
    # the listener runs as the interval ends: a zero-length marker here,
    # carrying the duration, puts the interval on the profiler's clock
    dur_ns = int(secs * 1e9)
    with TraceAnnotation(f"jax.{stage}", dur_ns=dur_ns, fun=fun_name):
        pass
    tracer = obs.tracer
    if tracer.enabled:
        now = tracer._wall_now()
        tracer.add_span(f"jax.{stage}", now - secs, now, lane=_WALL_LANE,
                        cat="jax", args={"fun": fun_name})


def watch_jax() -> None:
    """Install the compile-pipeline listener once per process."""
    if _watch["installed"]:
        return
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _watch["installed"] = True


watch_jax()
