"""repro.obs: tracer/metrics semantics, the zero-overhead no-op contract,
Chrome-trace export validity, and the bit-for-bit pin of the registry
refactor against the legacy ``*_mbits`` History accounting."""
import json
import math

import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro import fl, obs
from repro.core.fedavg import FLConfig
from repro.obs import profile
from repro.obs.context import Obs
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.tracer import NOOP_TRACER, Tracer
from repro.pon import PonConfig
from repro.pon.dba import make_dba
from repro.pon.events import Topology, UpstreamJob, UpstreamSim


# ------------------------------------------------------------------ tracer

def test_spans_nest_and_close_on_sim_clock():
    t = Tracer()
    sim = {"now": 0.0}
    clock = lambda: sim["now"]
    with t.span("outer", lane=("fl", "rounds"), clock=clock):
        sim["now"] = 1.0
        with t.span("inner", clock=clock):
            sim["now"] = 2.0
        sim["now"] = 3.0
    assert t._depth == 0
    by_name = {s.name: s for s in t.spans}
    # inner closes first and nests strictly inside outer
    assert [s.name for s in t.spans] == ["inner", "outer"]
    assert (by_name["outer"].t0_s, by_name["outer"].t1_s) == (0.0, 3.0)
    assert by_name["outer"].t0_s <= by_name["inner"].t0_s
    assert by_name["inner"].t1_s <= by_name["outer"].t1_s


def test_wall_spans_unaffected_by_sim_offset():
    t = Tracer()
    t.offset_s = 1000.0          # retro per-round shift on the sim axis
    t.add_span("sim", 0.0, 1.0)
    with obs.use(Obs(tracer=t)), profile.span("host-work"):
        pass
    sim_span, wall_span = t.spans
    assert (sim_span.t0_s, sim_span.t1_s) == (1000.0, 1001.0)
    # wall spans stay near wall-0 — offset_s must not leak onto wall lanes
    assert wall_span.lane[0] == "wall"
    assert 0.0 <= wall_span.t0_s <= wall_span.t1_s < 100.0


def test_non_finite_spans_and_instants_are_dropped():
    t = Tracer()
    t.add_span("bad", float("nan"), 1.0)
    t.add_span("bad", 0.0, float("inf"))
    t.instant("bad", float("nan"))
    t.counter("bad", float("inf"), {"v": 1})
    assert not t.spans and not t.instants and not t.counters


def test_chrome_export_schema(tmp_path):
    t = Tracer()
    t.add_span("grant", 1.0, 2.0, lane=("pon0", "onu3"), cat="grant",
               args={"wavelength": 0})
    t.instant("server-update", 2.5, lane=("server", "agg"))
    t.counter("dba", 1.5, {"queue_depth": 4}, lane=("pon0", "dba"))
    doc = t.to_chrome()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    phases = [e["ph"] for e in doc["traceEvents"]]
    assert set(phases) <= {"X", "i", "C", "M"}
    # lane labels are interned to int pid/tid with metadata naming them
    names = {(e["name"], e["args"]["name"]) for e in doc["traceEvents"]
             if e["ph"] == "M"}
    assert ("process_name", "pon0") in names
    assert ("thread_name", "onu3") in names
    x = [e for e in doc["traceEvents"] if e["ph"] == "X"][0]
    assert (x["ts"], x["dur"]) == (1.0e6, 1.0e6)   # microseconds
    assert isinstance(x["pid"], int) and isinstance(x["tid"], int)
    p = t.write(str(tmp_path / "trace.json"))
    assert json.load(open(p)) == json.loads(json.dumps(doc))


def test_noop_tracer_is_allocation_free_on_hot_paths():
    assert not NOOP_TRACER.enabled
    # span contexts are one shared singleton — no per-call allocation
    assert NOOP_TRACER.span("x") is NOOP_TRACER.span("y")
    NOOP_TRACER.add_span("x", 0, 1)
    assert NOOP_TRACER.spans == ()
    assert NOOP_TRACER.to_chrome()["traceEvents"] == []
    # the event simulator drops a disabled tracer entirely: the per-event
    # completion path must not even test it
    sim = UpstreamSim(Topology.uniform(2, 1, 1), make_dba("fifo"),
                      tracer=NOOP_TRACER)
    assert sim._tracer is None


def test_upstream_sim_emits_grant_spans_when_enabled():
    t = Tracer()
    sim = UpstreamSim(Topology.uniform(3, 1, 1), make_dba("fifo"), tracer=t)
    for i in range(3):
        sim.submit(UpstreamJob(seq=i, onu=i, size_mbits=100.0,
                               ready_s=float(i)))
    sim.drain()
    grants = [s for s in t.spans if s.cat == "grant"]
    assert len(grants) == 3
    assert {s.lane for s in grants} == {("pon", f"onu{i}") for i in range(3)}
    for s in grants:
        assert math.isfinite(s.t0_s) and s.t1_s > s.t0_s
        assert s.args["size_mbits"] == 100.0


# ----------------------------------------------------------------- metrics

def test_counter_take_is_bit_for_bit_with_legacy_accumulator():
    c = Counter("pon.upstream_mbits")
    legacy_total = 0.0
    for v in (211.32, 0.1, 0.2, 1e-9, 3381.12):
        c.add(v)
        legacy_total += v
        # a single add into the drained window returns the EXACT float
        # (0.0 + v == v): History rows cannot drift from the old path
        assert c.take() == v
    # the total follows the identical += sequence as the legacy float
    assert c.total == legacy_total
    c.add(1.0)
    c.add(2.0)
    assert c.peek() == 3.0 and c.take() == 3.0 and c.peek() == 0.0


@settings(max_examples=40)
@given(adds=st.lists(st.tuples(st.floats(min_value=0.0, max_value=1e4),
                               st.booleans()),
                     min_size=0, max_size=60))
def test_counter_total_equals_sum_of_drained_windows(adds):
    """Property: under ANY interleaving of add() and take(), the monotonic
    total equals the sum of every drained window plus whatever is still
    pending — the invariant that makes History rows (windows) and run
    totals two readouts of one accumulator."""
    c = Counter("prop")
    windows = []
    n_adds = 0
    for v, do_take in adds:
        c.add(v)
        n_adds += 1
        if do_take:
            w = c.take()
            assert w >= 0.0
            windows.append(w)
    windows.append(c.take())         # final drain picks up the remainder
    # equality up to float associativity: the windows are partial sums of
    # the same add sequence, re-summed in grouped order
    assert math.isclose(c.total, math.fsum(windows),
                        rel_tol=1e-12, abs_tol=1e-9)
    assert c.n == n_adds
    assert c.peek() == 0.0 and c.take() == 0.0
    # the bit-for-bit case the drivers rely on: draining after EVERY add
    # returns each added float exactly (0.0 + v == v)
    c2 = Counter("prop-exact")
    for v, _ in adds:
        c2.add(v)
        assert c2.take() == v


def test_histogram_reservoir_is_deterministic_and_unbiased():
    """Satellite: the seeded reservoir keeps exact count/sum, can retain
    late observations (the old stride scheme silently dropped the tail),
    and two identical observation sequences export identical samples."""
    h1 = Histogram("pin", max_samples=32)
    h2 = Histogram("pin", max_samples=32)
    vals = [float(v) for v in range(500)]
    for v in vals:
        h1.observe(v)
        h2.observe(v)
    # exact moments over EVERY observation, not just the reservoir
    assert h1.count == 500 and h1.sum == sum(vals)
    assert (h1.min, h1.max) == (0.0, 499.0)
    # determinism: same name + same sequence -> identical reservoir,
    # hence identical exported quantiles, bit for bit
    assert h1.samples == h2.samples
    assert h1.to_dict() == h2.to_dict()
    # unbiased: observations past max_samples must be reachable (Algorithm
    # R replaces uniformly; 468 tail values vs 32 slots makes retention of
    # at least one tail value overwhelmingly likely for any fixed seed)
    assert any(v >= 32 for v in h1.samples)
    # a different metric name seeds a different (still valid) reservoir
    h3 = Histogram("other", max_samples=32)
    for v in vals:
        h3.observe(v)
    assert h3.count == h1.count and h3.sum == h1.sum


def test_histogram_merge_preserves_exact_moments():
    a = Histogram("m", max_samples=16)
    b = Histogram("m", max_samples=16)
    for v in range(40):
        a.observe(float(v))
    for v in range(40, 100):
        b.observe(float(v))
    a.merge_from(b)
    assert a.count == 100 and a.sum == sum(range(100))
    assert (a.min, a.max) == (0.0, 99.0)
    assert len(a.samples) <= 16


def test_gauge_and_histogram_summaries():
    g = Gauge("g")
    for v in (3.0, 1.0, 2.0):
        g.set(v)
    assert (g.value, g.min, g.max) == (2.0, 1.0, 3.0)
    h = Histogram("h", max_samples=64)
    for v in range(1000):
        h.observe(float(v))
    assert h.count == 1000 and len(h.samples) <= 64
    assert h.min == 0.0 and h.max == 999.0
    assert h.quantile(0.0) <= h.quantile(0.5) <= h.quantile(1.0)
    d = h.to_dict()
    assert d["kind"] == "histogram" and d["count"] == 1000
    # empty instruments export honest nulls, not fake zeros
    assert Histogram("e").to_dict()["min"] is None
    assert Gauge("e").to_dict()["min"] is None


def test_registry_jsonl_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("pon.upstream_mbits").add(1479.296)
    reg.gauge("fl.n_pons").set(4.0)
    reg.histogram("fl.involved").observe(13.0)
    p = reg.write_jsonl(str(tmp_path / "m.jsonl"))
    back = obs.read_jsonl(p)
    assert [r["name"] for r in back] == ["pon.upstream_mbits", "fl.n_pons",
                                        "fl.involved"]
    assert all(r["obs_schema"] == obs.SCHEMA for r in back)
    assert back[0]["total"] == 1479.296
    assert reg.summary()["pon.upstream_mbits"] == 1479.296
    assert reg.names() == sorted(r["name"] for r in back)


# --------------------------------------------- drivers: the bit-for-bit pin

def _transport_loop(mode: str, n_pons: int = 1, rounds: int = 3,
                    obs_bundle=None):
    pon = PonConfig(n_onus=4, clients_per_onu=5, n_pons=n_pons)
    flc = FLConfig(n_onus=4, clients_per_onu=5, n_pons=n_pons,
                   n_selected=8 * n_pons, pon=pon)
    counts = np.random.default_rng(0).integers(
        50, 400, flc.n_clients).astype(np.float32)
    onu = np.arange(flc.n_clients) // flc.clients_per_onu
    skw = fl.filter_strategy_kwargs(mode, {"n_pons": n_pons})
    backend = fl.TransportBackend(fl.make_strategy(mode, **skw), counts, onu)
    exp = fl.ExperimentConfig(fl=flc, strategy=fl.canonical_name(mode),
                              strategy_kwargs=tuple(sorted(skw.items())),
                              n_rounds=rounds, seed=3)
    loop = fl.RoundLoop(exp, backend, obs=obs_bundle)
    return loop, loop.run()


@pytest.mark.parametrize("mode,n_pons", [("classical", 1), ("sfl", 1),
                                         ("hier_sfl", 2)])
def test_registry_totals_match_history_mbits_bit_for_bit(mode, n_pons):
    """The refactored counters ARE the accounting: totals must equal the
    History column sums exactly (float ==, not allclose)."""
    loop, hist = _transport_loop(mode, n_pons=n_pons)
    reg = loop.metrics
    assert reg.counter("pon.upstream_mbits").total == \
        sum(hist.column("upstream_mbits"))
    assert loop.total_upstream_mbits == \
        reg.counter("pon.upstream_mbits").total
    if n_pons > 1:   # hier transport also feeds the metro/trunk segments
        assert reg.counter("metro.mbits").total == \
            sum(hist.column("metro_mbits"))
        assert reg.counter("trunk.mbits").total == \
            sum(hist.column("trunk_mbits"))
        assert reg.gauge("fl.n_pons").value == n_pons
        # gauges hold the last round's per-segment peaks, set-then-read
        assert reg.gauge("pon.mbits_max").value == \
            hist.column("pon_mbits_max")[-1]
        assert reg.gauge("metro.mbits_max").value == \
            hist.column("metro_mbits_max")[-1]
    assert reg.histogram("fl.involved").count == len(hist)


@pytest.mark.parametrize("mode,n_pons", [("classical", 1), ("sfl", 1),
                                         ("hier_sfl", 2)])
def test_tracing_changes_no_history_values(mode, n_pons):
    """An enabled tracer must be a pure observer: History rows (keys AND
    values) identical to a disabled run, bit for bit."""
    _, base = _transport_loop(mode, n_pons=n_pons)
    enabled = Obs.enabled_tracing()
    with obs.use(enabled):
        _, traced = _transport_loop(mode, n_pons=n_pons)
    assert len(enabled.tracer.spans) > 0       # it really did trace
    assert len(base) == len(traced)
    for a, b in zip(base, traced):
        assert set(a) == set(b)                # no extra History keys
        for k in a:
            va, vb = a[k], b[k]
            if isinstance(va, float) and math.isnan(va):
                assert math.isnan(vb)
            else:
                assert va == vb, (k, va, vb)


def _transport_orchestrator(policy: str, rounds: int = 4):
    from repro import runtime
    pon = PonConfig(n_onus=4, clients_per_onu=5)
    flc = FLConfig(n_onus=4, clients_per_onu=5, n_selected=8, pon=pon)
    counts = np.random.default_rng(0).integers(
        50, 400, flc.n_clients).astype(np.float32)
    onu = np.arange(flc.n_clients) // flc.clients_per_onu
    backend = fl.TransportBackend(fl.make_strategy("sfl"), counts, onu)
    exp = fl.ExperimentConfig(fl=flc, strategy="sfl_two_step",
                              n_rounds=rounds, seed=3, policy=policy)
    orch = runtime.Orchestrator(exp, backend)
    return orch, orch.run()


@pytest.mark.parametrize("policy", ["semi_sync", "fedbuff"])
def test_tracing_changes_no_history_values_async_policies(policy):
    """PR 6 pinned traced-vs-untraced equality on the sync paths only;
    the async Orchestrator policies get the identical guarantee: an
    enabled tracer is a pure observer of semi_sync/fedbuff rows too."""
    _, base = _transport_orchestrator(policy)
    enabled = Obs.enabled_tracing()
    with obs.use(enabled):
        _, traced = _transport_orchestrator(policy)
    assert len(enabled.tracer.spans) > 0       # it really did trace
    assert len(base) == len(traced)
    for a, b in zip(base, traced):
        assert set(a) == set(b)                # no extra History keys
        for k in a:
            va, vb = a[k], b[k]
            if isinstance(va, float) and math.isnan(va):
                assert math.isnan(vb)
            else:
                assert va == vb, (k, va, vb)


def test_round_loop_trace_covers_grants_and_tiers():
    """A traced hier round carries per-ONU grant spans and per-tier
    aggregation windows on the one global timeline."""
    enabled = Obs.enabled_tracing()
    with obs.use(enabled):
        _transport_loop("hier_sfl", n_pons=2, rounds=2)
    spans = enabled.tracer.spans
    cats = {s.cat for s in spans}
    assert {"grant", "agg", "client", "round"} <= cats
    grant_lanes = {s.lane for s in spans if s.cat == "grant"}
    assert any(l[0].startswith("pon") and l[1].startswith("onu")
               for l in grant_lanes)
    assert any(l == ("metro", "olt0") or l[1].startswith("olt")
               for l in grant_lanes)
    names = {s.name for s in spans}
    assert {"θ-gather", "Φ-gather", "Ψ-agg", "round"} <= names
    # round 1 is offset onto the global timeline: its round span starts
    # one deadline window after round 0's
    rounds = sorted(s.t0_s for s in spans if s.name == "round")
    window = PonConfig(n_onus=4, clients_per_onu=5,
                       n_pons=2).sync_threshold_s
    assert rounds == [0.0, window]
    # everything exports
    doc = enabled.tracer.to_chrome()
    assert len(doc["traceEvents"]) > len(spans)


def test_replay_is_invisible_to_obs():
    """Resume fast-forward must neither emit spans nor skew metrics."""
    enabled = Obs.enabled_tracing()
    loop, hist = _transport_loop("sfl")
    rng = np.random.default_rng(loop.cfg.seed)
    with obs.use(enabled):
        fl.loop.replay_sync_round(loop.cfg, loop.backend,
                                  loop.cfg.make_failure_model(), rng, 0)
    assert enabled.tracer.spans == []
    assert enabled.metrics.names() == []
    # and the replayed rng stream really is the live round's stream
    rec = fl.loop.sync_round(loop.cfg, loop.backend,
                             loop.cfg.make_failure_model(),
                             np.random.default_rng(loop.cfg.seed), 0)
    assert rec["upstream_mbits"] == hist.column("upstream_mbits")[0]


# --------------------------------------------------------------- session/CLI

def test_session_from_cli_args_writes_artifacts(tmp_path):
    import argparse
    ap = argparse.ArgumentParser()
    obs.add_obs_cli_args(ap)
    trace_p = str(tmp_path / "trace.json")
    metrics_p = str(tmp_path / "m.jsonl")
    args = ap.parse_args(["--trace-out", trace_p,
                          "--metrics-out", metrics_p])
    prev = obs.get()
    sess = obs.session_from_args(args)
    try:
        assert obs.get() is sess.obs and sess.tracer.enabled
        with obs.use(sess.obs):
            _transport_loop("sfl", obs_bundle=sess.obs)
    finally:
        sess.finish(quiet=True)
    assert obs.get() is prev                   # context restored
    doc = json.load(open(trace_p))
    assert doc["traceEvents"]
    assert any(r["name"] == "pon.upstream_mbits"
               for r in obs.read_jsonl(metrics_p))


def test_disabled_session_is_noop_and_writes_nothing(tmp_path):
    prev = obs.get()
    sess = obs.session()                       # no outputs requested
    try:
        assert not sess.tracer.enabled
        assert obs.get() is sess.obs
    finally:
        sess.finish(quiet=True)
    assert obs.get() is prev
    assert list(tmp_path.iterdir()) == []


# ------------------------------------------------------- program spans / jax

def test_profile_span_is_a_bare_trace_annotation_when_untraced():
    """No tracer: the span costs what a TraceAnnotation costs."""
    import jax
    with obs.use(Obs.disabled()):
        sp = profile.span("train.chunk", chunk=0, rows=16)
        assert type(sp) is jax.profiler.TraceAnnotation
        with sp:
            sp.set_metadata(extra=1)


def test_profile_span_puts_its_stats_on_the_wall_lane():
    t = Tracer()
    with obs.use(Obs(tracer=t)):
        with profile.span("round.minibatch", n_clients=4) as sp:
            sp.set_metadata(h2d_bytes=128)
        out, dt = profile.timed("backend.eval_s", lambda: 1.0)
    assert out == 1.0 and dt >= 0
    mb, ev = t.spans
    assert (mb.name, mb.lane, mb.args) == (
        "round.minibatch", ("wall", "compute"),
        {"n_clients": 4, "h2d_bytes": 128})
    assert ev.name == "backend.eval_s" and ev.args is None
    assert mb.t1_s <= ev.t0_s


def test_fresh_jit_counts_one_program_into_metrics_out(tmp_path):
    """A jit made afresh traces, lowers and compiles (or reads the
    persistent cache) once; calling it again obtains nothing; operators
    read the jax.* counters from --metrics-out. Installing the listener
    again adds none: each event still counts once."""
    import jax
    import jax.numpy as jnp

    profile.watch_jax()

    names = [n for pair in profile.COUNTERS.values() for n in pair]
    path = str(tmp_path / "m.jsonl")
    x = jnp.ones((3, 5))
    sess = obs.session(metrics_out=path)
    try:
        reg = sess.metrics
        f = jax.jit(lambda v: jax.lax.neg(v))
        f(x).block_until_ready()
        first = {n: reg.counter(n).total for n in names}
        f(x).block_until_ready()
        second = {n: reg.counter(n).total for n in names}
    finally:
        sess.finish(quiet=True)
    assert first["jax.traces"] == 1 and first["jax.lowerings"] == 1
    assert first["jax.compiles"] + first["jax.cache_reads"] == 1
    assert first["jax.compile_s"] + first["jax.cache_read_s"] > 0
    assert second == first
    totals = {r["name"]: r["total"] for r in obs.read_jsonl(path)}
    assert totals["jax.lowerings"] == 1
