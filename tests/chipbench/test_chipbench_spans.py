"""The program's spans and JAX's compile markers as the benchmark reads
them (``chipbench/progtrace.py``, the readers that use it and
``chipbench/attribute.py``): in a tiny round recorded here on the CPU, on
made-up intervals, and in a trace recorded on the chip by
``record_span_trace.py``."""
from __future__ import annotations

import numpy as np
import pytest

from chipbench_tiny import ROOT, SEED, load_module, make_tree, run
from chipbench import attribute as at
from chipbench import progtrace as pt
from chipbench import trace as tr

MS = 1e6
NEW = ("transport_s.round", "minibatch_s.round", "jit_programs.round",
       "jit_host_s.round", "train_idle_s.round")


def read(name: str, ctx):
    return load_module(ROOT / "chipbench/metrics" / f"{name}.py").read(ctx)


@pytest.fixture(scope="module")
def tiny_traced(tmp_path_factory):
    root = make_tree(tmp_path_factory.mktemp("spans"))
    result = run(root, trace=True)
    path = tr.find(root / pt.TRACE_DIR)
    return root, result, tr.load(path), pt.load(path)


def test_round_spans_nest_with_their_stats(tiny_traced):
    """Each round of the tiny cell opens round.transport, round.minibatch
    and round.aggregate once, in that order around backend.train_s, and
    one train.chunk per client chunk inside it, each with its stats."""
    import jax
    import jax.numpy as jnp

    from chipbench.gen import femnist as gen

    root, _, t, p = tiny_traced
    assert p.instrumented and t.rounds() == p.rounds
    clients, _ = gen.generate(8, SEED)
    one = gen.client_minibatches(np.random.default_rng(0), clients[0], 2, 10)
    per_client = sum(x.nbytes for x in jax.tree.leaves(
        jax.tree.map(jnp.asarray, one)))
    for r0, r1 in t.rounds():
        inside = lambda ivs: [iv for iv in ivs if r0 <= iv[0] <= iv[1] <= r1]
        (tp,) = inside(p.spans["round.transport"])
        (mb,) = inside(p.spans["round.minibatch"])
        (ag,) = inside(p.spans["round.aggregate"])
        (train,) = inside(t.spans["backend.train_s"])
        chunks = inside(p.spans["train.chunk"])
        assert tp[1] <= mb[0] <= mb[1] <= train[0] <= train[1] <= ag[0]
        assert all(train[0] <= s <= e <= train[1] for s, e, _ in chunks)
        assert [st["chunk"] for *_, st in chunks] == list(range(len(chunks)))
        assert tp[2]["n_selected"] == 6 and 1 <= tp[2]["involved"] <= 6
        n = mb[2]["n_clients"]
        assert n == -(-int(tp[2]["involved"]) // 4) * 4
        assert [st["rows"] for *_, st in chunks] == [4] * (n // 4)
        assert mb[2]["h2d_bytes"] == n * per_client


def test_new_readers_read_the_tiny_round(tiny_traced):
    """In the harness's own traced run (the readers find the trace again
    by its rounds), each new reader reads the recorded round; the chunk's
    vmapped scan is traced anew each call, so JAX obtains programs."""
    _, result, _, _ = tiny_traced
    got = {k: v["value"] for k, v in result["metrics"].items()}
    for name in ("transport_s.round", "minibatch_s.round",
                 "jit_host_s.round"):
        assert got[name] > 0, name
    assert got["jit_programs.round"] >= 1
    assert "train_idle_s.round" not in got          # the CPU: no op line


def test_compile_marker_lands_inside_its_span(tmp_path):
    """A fresh jit inside a span: its trace, lowering and compile (or
    cache read) are each one interval inside the span, to within 1 ms;
    called again in the next span, it obtains nothing."""
    import jax
    import jax.numpy as jnp

    from repro.obs import profile

    x = jnp.ones((7, 3))
    f = jax.jit(lambda v: jax.lax.neg(v))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.StepTraceAnnotation(tr.ROUND_SPAN, step_num=0):
        for c in range(2):
            with profile.span("train.chunk", chunk=c, rows=7):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    p = pt.load(tr.find(tmp_path))
    (first, second) = sorted(p.spans["train.chunk"])
    assert (first[2], second[2]) == ({"chunk": 0, "rows": 7},
                                     {"chunk": 1, "rows": 7})
    stages = [(n, iv) for n in pt.JAX_STAGES for iv in p.spans.get(n, [])]
    assert sum(n == "jax.trace" for n, _ in stages) == 1
    assert sum(n == "jax.lower" for n, _ in stages) == 1
    assert sum(n in ("jax.compile", "jax.cache_read") for n, _ in stages) == 1
    for name, (s, e, st) in stages:
        assert first[0] - MS <= s <= e <= first[1] + MS, name
        assert e - s == st["dur_ns"] and st["fun"], name
        assert not second[0] <= e <= second[1], name


def _made_up(instrumented: bool):
    """One 10 ms round: transport 0-1.5, the driver 1.5-2, minibatches
    2-3; backend.train_s 3-9 with chunk 0 (3-4) and chunk 1 (6-8), whose
    fresh program is traced 6-6.5 and read from the cache 6.5-7.5; the
    aggregate 9-9.5 and eval 9.5-10. The device runs 4-6 and 8-9."""
    t = tr.Trace(
        ops={"/device:TPU:0": [("mm", 4 * MS, 6 * MS), ("mm", 8 * MS,
                                                        9 * MS)]},
        spans={tr.ROUND_SPAN: [(0, 10 * MS)],
               "backend.train_s": [(3 * MS, 9 * MS)],
               "backend.eval_s": [(9.5 * MS, 10 * MS)]})
    if instrumented:
        p = pt.Program(spans={
            "round.transport": [(0, 1.5 * MS, {"n_selected": 8,
                                               "involved": 6})],
            "round.minibatch": [(2 * MS, 3 * MS, {"n_clients": 8,
                                                  "h2d_bytes": 3 << 20})],
            "train.chunk": [(3 * MS, 4 * MS, {"chunk": 0, "rows": 4}),
                            (6 * MS, 8 * MS, {"chunk": 1, "rows": 4})],
            "jax.trace": [(6 * MS, 6.5 * MS, {"fun": "f"})],
            "jax.cache_read": [(6.5 * MS, 7.5 * MS, {"fun": "jit(f)"})],
            "round.aggregate": [(9 * MS, 9.5 * MS, {})]},
            calls={}, rounds=[(0, 10 * MS)])
    else:
        p = pt.Program(spans={}, calls={
            "loop.py:_transport_stage": [(0, 1.5 * MS)],
            "backends.py:run_round": [(2 * MS, 9.2 * MS)],
            "partial_eval.py:trace_to_jaxpr": [(6 * MS, 6.5 * MS)],
            "compiler.py:compile_or_get_cached": [(6.5 * MS, 7.5 * MS)]},
            rounds=[(0, 10 * MS)])
    return {"trace": t, "rounds": [{}], "chips": 1, "program": p}


@pytest.mark.parametrize("instrumented", [True, False],
                         ids=["program_spans", "python_calls"])
def test_new_readers_on_made_up_intervals(instrumented):
    """The program's spans, or on a program without them the Python calls
    they wrap, give the same readings."""
    ctx = _made_up(instrumented)
    assert read("transport_s.round", ctx) == pytest.approx(0.0015)
    assert read("minibatch_s.round", ctx) == pytest.approx(0.001)
    assert read("jit_programs.round", ctx) == 1
    assert read("jit_host_s.round", ctx) == pytest.approx(0.0015)
    assert read("train_idle_s.round", ctx) == pytest.approx(0.003)


def test_new_readers_find_nothing_without_spans_or_calls():
    ctx = _made_up(True)
    ctx["program"] = pt.Program(spans={}, calls={}, rounds=[(0, 10 * MS)])
    for name in NEW[:4]:
        assert read(name, ctx) is None, name
    ctx["program"] = None           # the trace could not be found again
    for name in NEW[:4]:
        assert read(name, ctx) is None, name


def test_attribution_names_every_idle_gap():
    ctx = _made_up(True)
    out = at.attribute(ctx["trace"], ctx["program"])
    idle = out["idle"]
    assert idle["backend.train_s"] == pytest.approx(
        {"total": 0.003, "train.chunk": 0.0015, "jax.cache_read": 0.001,
         "jax.trace": 0.0005})
    assert idle[tr.NO_SPAN] == pytest.approx(
        {"total": 0.0035, "round.transport": 0.0015,
         "round.minibatch": 0.001, "driver": 0.0005,
         "round.aggregate": 0.0005})
    assert idle["backend.eval_s"] == pytest.approx({"total": 0.0005,
                                                    "driver": 0.0005})
    # longest first; equal lengths in time order
    assert [g[0] for g in out["idle_gaps"][:4]] == [
        f"{tr.NO_SPAN}/round.transport", f"{tr.NO_SPAN}/round.minibatch",
        "backend.train_s/train.chunk",
        "backend.train_s/train.chunk/jax.cache_read"]
    assert [g[1] for g in out["idle_gaps"][:4]] == pytest.approx(
        [0.0015, 0.001, 0.001, 0.001])
    assert out["h2d_mib"] == 3
    assert out["load"] == {"n_selected": 8, "involved": 6, "n_clients": 8,
                           "chunks": 2, "rows": 4}
    assert out["chunk_idle_s"] == pytest.approx({0: 0.001, 1: 0.002})
    assert out["jax"]["jax.cache_read"]["count"] == 1
    assert out["jax"]["union_s"] == pytest.approx(0.0015)


# a trace recorded on a TPU v5e by record_span_trace.py: two rounds, each
# with a fresh jit compiled inside the first train.chunk
CHIP_TRACE = ROOT / "tests/chipbench/span_trace.xplane.pb"


@pytest.fixture(scope="module")
def chip():
    return {"trace": tr.load(CHIP_TRACE), "program": pt.load(CHIP_TRACE),
            "rounds": [{}, {}], "chips": 1}


def test_existing_readers_read_the_chip_trace_unchanged(chip):
    """The readers that read the trace alone, on a chip trace that has the
    program spans: the values are pinned here. (Those readers and
    ``trace.py`` do not read the program spans; their values on the
    made-up and recorded traces without them are pinned in
    ``test_chipbench_trace.py``.)"""
    assert chip["trace"].devices == ["/device:TPU:0"]
    assert len(chip["trace"].rounds()) == 2
    pinned = {"device_idle_share": 99.47832670715289,
              "host_only_s.round": 0.02646348,
              "train_s.round": 0.159162082,
              "eval_s.round": 0.00058629,
              "aggregate_device_s.round": 0.0}
    for name, value in pinned.items():
        assert read(name, chip) == pytest.approx(value, rel=1e-12), name


def test_chip_trace_names_what_fills_the_idle_time(chip):
    t, p = chip["trace"], chip["program"]
    assert read("jit_programs.round", chip) == 1
    assert read("transport_s.round", chip) >= 0.010        # 10 ms sleep
    assert read("minibatch_s.round", chip) > 0
    train_idle = read("train_idle_s.round", chip)
    jit_host = read("jit_host_s.round", chip)
    assert 0 < jit_host <= train_idle
    out = at.attribute(t, p)
    in_train = out["idle"]["backend.train_s"]
    assert in_train["total"] == pytest.approx(train_idle)
    named = sum(v for k, v in in_train.items()
                if k.startswith("jax.") or k == "train.chunk")
    assert named >= 0.9 * train_idle
    host_only = out["idle"][tr.NO_SPAN]
    assert host_only["total"] == pytest.approx(read("host_only_s.round",
                                                    chip))
    # the 10 ms sleep with no span open is labelled "driver"
    assert host_only["driver"] == pytest.approx(0.010, abs=0.002)
    assert out["h2d_mib"] == 2
    assert out["load"] == {"n_selected": 8, "involved": 6, "n_clients": 16,
                           "chunks": 2, "rows": 16}
    # the fresh jit compiles in chunk 0: its idle time is there
    assert out["chunk_idle_s"][0] > 10 * out["chunk_idle_s"][1]
    assert [g[0] for g in out["idle_gaps"][:2]] == [
        "backend.train_s/train.chunk/jax.compile"] * 2
    chunks = [(s, e) for s, e, st in p.spans["train.chunk"]
              if st["chunk"] == 0]
    for s, e in (iv for stage in pt.JAX_STAGES[1:] for iv in p.jax(stage)):
        assert any(c0 - MS <= s <= e <= c1 + MS for c0, c1 in chunks)
