"""The trace reduction and the per-layer readers on made-up intervals.

Each interval stands for a device op or a host span as
``chipbench.trace.load`` reads them from a profiler trace: device op
events per device, and the program's and the harness's spans by name.
"""
from __future__ import annotations

import pytest

from chipbench_tiny import ROOT, load_module
from chipbench import trace as tr

MS = 1e6


def test_union_merges_overlaps_and_gaps_fill_the_rest():
    busy = tr.union([(5, 8), (0, 2), (1, 3), (8, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert tr.gaps(busy, -1, 12) == [(-1, 0), (3, 5), (9, 12)]
    assert tr.length(tr.intersect(busy, [(2, 6)])) == 2
    assert tr.clip(busy, 1, 6) == [(1, 3), (5, 6)]


def _made_up():
    return tr.Trace(
        ops={"/device:TPU:0": [("fusion", 0, 4 * MS), ("dot", 6 * MS,
                                                       9 * MS)]},
        spans={tr.ROUND_SPAN: [(0, 10 * MS)],
               "backend.train_s": [(0, 5 * MS)],
               "backend.eval_s": [(8 * MS, 10 * MS)]})


def test_idle_gaps_are_labelled_by_the_open_span():
    t = _made_up()
    assert t.window() == (0, 10 * MS)
    assert tr.idle_gaps(t, "/device:TPU:0") == [
        ("backend.train_s", 1 * MS), (tr.NO_SPAN, 1 * MS),
        ("backend.eval_s", 1 * MS)]
    assert tr.top_ops(t) == [("fusion", 0.004), ("dot", 0.003)]


def test_layer_metrics_on_made_up_intervals():
    t = _made_up()
    ctx = {"trace": t, "rounds": [{"involved": 1}], "chips": 1}
    read = lambda name: load_module(
        ROOT / "chipbench/metrics" / f"{name}.py").read(ctx)
    assert read("device_idle_share") == pytest.approx(30.0)
    assert read("host_only_s.round") == pytest.approx(0.001)   # 5..6 ms
    assert read("train_s.round") == pytest.approx(0.005)
    assert read("eval_s.round") == pytest.approx(0.002)
    assert read("aggregate_device_s.round") == pytest.approx(0.002)


def test_readers_find_nothing_in_an_empty_trace():
    ctx = {"trace": tr.Trace(ops={}, spans={}), "rounds": [], "chips": 1}
    for path in sorted((ROOT / "chipbench/metrics").glob("*.py")):
        assert load_module(path).read(ctx) is None, path.name


def test_load_reads_host_spans_from_a_recorded_trace(tmp_path):
    """A trace recorded here on the CPU: the rounds and backend spans come
    back with their nesting; the CPU has no device op line to read."""
    import time

    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for i in range(2):
        with jax.profiler.StepTraceAnnotation(tr.ROUND_SPAN, step_num=i):
            with jax.profiler.TraceAnnotation("backend.train_s"):
                f(x).block_until_ready()
            time.sleep(0.01)
            with jax.profiler.TraceAnnotation("backend.eval_s"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(tr.find(tmp_path))
    assert t.devices == []
    rounds = t.rounds()
    assert len(rounds) == 2
    for name in tr.BACKEND_SPANS:
        spans = t.spans[name]
        assert len(spans) == 2
        assert all(any(r0 <= s < e <= r1 for r0, r1 in rounds)
                   for s, e in spans)
    lo, hi = t.window()
    assert hi - lo >= 0.02 * 1e9
