"""repro.obs.audit: health monitors, run bundles, the diff engine, and
the bench regression gate.

The acceptance criteria from the audit layer's design: two bundles from
the same config+seed diff to ZERO (the bit-for-bit pins make the diff a
sharp instrument), differing seeds localize the first diverging round,
and an injected bench regression makes ``benchmarks.regress`` exit
nonzero while the committed baseline passes clean.
"""
import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import fl, obs
from repro.core.fedavg import FLConfig
from repro.obs.audit import (
    BandwidthBudgetMonitor,
    ConvergenceStallMonitor,
    DeadlineMissMonitor,
    HealthEngine,
    Incident,
    RunReport,
    StragglerOnuMonitor,
    TrunkFlatnessMonitor,
    config_dict,
    config_hash,
    diff_bundles,
    render_diff_html,
    render_timeline_svg,
)
from repro.obs.audit.health import INCIDENT_SCHEMA, default_monitors
from repro.obs.context import Obs
from repro.obs.tracer import Span, Tracer
from repro.pon import PonConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------ helpers

def _exp(seed=3, mode="sfl", n_pons=1, rounds=3):
    pon = PonConfig(n_onus=4, clients_per_onu=5, n_pons=n_pons)
    flc = FLConfig(n_onus=4, clients_per_onu=5, n_pons=n_pons,
                   n_selected=8 * n_pons, pon=pon)
    skw = fl.filter_strategy_kwargs(mode, {"n_pons": n_pons})
    return fl.ExperimentConfig(fl=flc, strategy=fl.canonical_name(mode),
                               strategy_kwargs=tuple(sorted(skw.items())),
                               n_rounds=rounds, seed=seed)


def _backend(exp, mode="sfl"):
    flc = exp.fl
    counts = np.random.default_rng(0).integers(
        50, 400, flc.n_clients).astype(np.float32)
    onu = np.arange(flc.n_clients) // flc.clients_per_onu
    return fl.TransportBackend(
        fl.make_strategy(mode, **dict(exp.strategy_kwargs)), counts, onu)


def _bundle(path, seed=3, mode="sfl", health=False):
    """One full driver run through an ObsSession with --report-out."""
    exp = _exp(seed=seed, mode=mode)
    sess = obs.session(report_out=str(path), health=health, driver="round_loop")
    try:
        loop = fl.RoundLoop(exp, _backend(exp, mode))
        hist = loop.run()
    finally:
        sess.finish(quiet=True, cfg=exp, history=hist)
    return RunReport.load(str(path))


# ------------------------------------------------------------- run bundles

def test_bundle_roundtrip_and_config_hash(tmp_path):
    rep = _bundle(tmp_path / "a.json")
    assert rep.schema == "repro.obs.audit/v1"
    assert rep.driver == "round_loop"
    assert rep.seed == 3
    assert len(rep.history) == 3
    assert rep.metrics and rep.summary
    assert rep.trace["traceEvents"]          # report_out implies a live trace
    assert rep.env["python"]
    # the hash is over the resolved config: same config -> same hash,
    # regardless of object identity
    d1 = config_dict(_exp(seed=3))
    d2 = config_dict(_exp(seed=3))
    assert d1 == d2 and config_hash(d1) == config_hash(d2)
    assert rep.config_hash == config_hash(d1)
    assert config_hash(config_dict(_exp(seed=4))) != rep.config_hash
    # nested dataclasses resolved to plain JSON (tuples -> lists)
    assert rep.config["fl"]["pon"]["n_onus"] == 4
    json.dumps(rep.to_dict())                # fully JSON-serializable


def test_bundle_load_rejects_foreign_schema(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"schema": "something/else"}))
    with pytest.raises(ValueError):
        RunReport.load(str(p))


# ------------------------------------------------------------- diff engine

def test_same_config_and_seed_diffs_to_zero(tmp_path):
    """Acceptance: two bundles from the identical config+seed report zero
    diffs — history, metrics, AND the sim-span timeline."""
    a = _bundle(tmp_path / "a.json", seed=3)
    b = _bundle(tmp_path / "b.json", seed=3)
    diff = diff_bundles(a, b)
    assert diff.config_delta == []
    assert diff.n_diffs == 0, [e.line() for e in diff.entries]
    assert diff.exit_code == 0
    assert diff.first_divergence["round"] is None


def test_differing_seeds_localize_first_diverging_round(tmp_path):
    a = _bundle(tmp_path / "a.json", seed=3)
    b = _bundle(tmp_path / "b.json", seed=4)
    diff = diff_bundles(a, b)
    assert diff.n_diffs > 0 and diff.exit_code == 1
    # config attribution: the only config field that moved is the seed
    assert [e.key for e in diff.config_delta] == ["seed"]
    # first divergence is the earliest diverging round in the History
    hard_rounds = []
    for ra, rb in zip(a.history, b.history):
        if any(ra.get(k) != rb.get(k)
               for k in set(ra) | set(rb)
               if not (isinstance(ra.get(k), float)
                       and isinstance(rb.get(k), float)
                       and math.isnan(ra[k]) and math.isnan(rb[k]))):
            hard_rounds.append(ra["round"])
    assert diff.first_divergence["round"] == min(hard_rounds)
    assert diff.first_divergence["round_key"]
    # and the span timelines diverge somewhere concrete
    assert diff.first_divergence["span"]


def test_diff_cli_exit_codes(tmp_path):
    from repro.obs.audit import diff as diff_mod
    a = str(tmp_path / "a.json")
    b = str(tmp_path / "b.json")
    c = str(tmp_path / "c.json")
    _bundle(a, seed=3)
    _bundle(b, seed=3)
    _bundle(c, seed=4)
    html_out = str(tmp_path / "report.html")
    assert diff_mod.main([a, b]) == 0
    assert diff_mod.main([a, c, "--html", html_out]) == 1
    text = open(html_out).read()
    assert "<svg" in text and "first diverging round" in text


def test_python_dash_m_repro_obs_diff_entrypoint(tmp_path):
    """The documented CLI shape: ``python -m repro.obs.diff A B``."""
    a = str(tmp_path / "a.json")
    _bundle(a, seed=3)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-m", "repro.obs.diff", a, a],
                       capture_output=True, text=True, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr
    assert "0 diffs" in r.stdout


def test_diff_flags_missing_metrics_and_row_counts(tmp_path):
    a = _bundle(tmp_path / "a.json", seed=3)
    b = _bundle(tmp_path / "b.json", seed=3)
    b.metrics = [m for m in b.metrics if m["name"] != "pon.upstream_mbits"]
    b.history = b.history[:-1]
    diff = diff_bundles(a, b)
    stats = {e.status for e in diff.entries}
    assert "missing_b" in stats
    assert any(e.key == "n_rounds" for e in diff.entries)


def test_wall_metrics_are_warn_only():
    a = RunReport(metrics=[{"kind": "histogram", "name": "wall.train_s",
                            "count": 2, "mean": 1.0}])
    b = RunReport(metrics=[{"kind": "histogram", "name": "wall.train_s",
                            "count": 2, "mean": 5.0}])
    diff = diff_bundles(a, b)
    assert diff.n_diffs == 0 and diff.n_warns == 1


# ---------------------------------------------------------- health monitors

def test_convergence_stall_fires_once_per_streak():
    m = ConvergenceStallMonitor(window=3, min_delta=1e-3)
    incs = []
    # improve, then 6 flat rounds: exactly ONE incident at the 3rd
    accs = [0.1, 0.5, 0.5, 0.5, 0.5, 0.5, 0.5]
    for i, acc in enumerate(accs):
        incs += m.on_round({"round": i, "acc": acc})
    assert len(incs) == 1
    assert incs[0].kind == "convergence_stall" and incs[0].round == 4
    # an improvement re-arms the detector
    assert m.on_round({"round": 7, "acc": 0.9}) == []
    for i in range(8, 11):
        incs2 = m.on_round({"round": i, "acc": 0.9})
    assert len(incs2) == 1


def test_deadline_miss_slo():
    m = DeadlineMissMonitor(max_miss_rate=0.5)
    assert m.on_round({"round": 0, "n_selected": 10, "involved": 6.0}) == []
    incs = m.on_round({"round": 1, "n_selected": 10, "involved": 2.0})
    assert len(incs) == 1 and incs[0].kind == "deadline_slo"
    assert incs[0].severity == "error"
    assert incs[0].data["miss_rate"] == pytest.approx(0.8)


def test_bandwidth_budget_against_oracle():
    from repro.pon.metro import expected_segment_mbits
    exp = _exp(mode="sfl")
    m = BandwidthBudgetMonitor(tol_rel=0.01)
    m.bind(exp)
    pon = exp.fl.pon_config()
    budget = expected_segment_mbits(
        "sfl", pon.model_mbits, exp.fl.n_selected,
        n_active_onus=min(exp.fl.n_selected, pon.total_onus),
        n_active_pons=pon.n_pons)["pon"]
    assert m.on_round({"round": 0, "upstream_mbits": budget}) == []
    incs = m.on_round({"round": 1, "upstream_mbits": budget * 1.5})
    assert len(incs) == 1 and incs[0].kind == "bandwidth_budget"
    assert incs[0].data["segment"] == "pon"


def test_trunk_flatness_hier_only():
    hier = _exp(mode="hier_sfl", n_pons=2)
    model = hier.fl.pon_config().model_mbits
    m = TrunkFlatnessMonitor()
    m.bind(hier)
    assert m.on_round({"round": 0, "trunk_mbits": model}) == []
    incs = m.on_round({"round": 1, "trunk_mbits": 2.0 * model})
    assert len(incs) == 1 and incs[0].kind == "trunk_flatness"
    # flat transports never arm the monitor
    m2 = TrunkFlatnessMonitor()
    m2.bind(_exp(mode="sfl"))
    assert m2.on_round({"round": 0, "trunk_mbits": 10.0 * model}) == []


def test_straggler_onu_from_synthetic_grant_spans():
    m = StragglerOnuMonitor(k_sigma=2.0, min_delay_s=0.5, min_grants=3)
    spans = []
    for onu in range(9):
        q = 5.0 if onu == 8 else 0.1      # onu8 queues 50x longer
        for g in range(5):
            spans.append(Span("grant", g, g + 0.5, ("pon", f"onu{onu}"),
                              cat="grant", args={"queue_s": q}))
    m.on_spans(spans)
    incs = m.finish()
    assert len(incs) == 1
    assert incs[0].kind == "straggler_onu"
    assert incs[0].data["lane"] == ["pon", "onu8"]


def test_health_engine_surfaces_incidents_in_history_and_jsonl(tmp_path):
    """Wired end-to-end: a deliberately impossible SLO fires every round,
    the History rows carry the per-round incident count, and the JSONL
    export carries the schema-stamped records."""
    exp = _exp()
    engine = HealthEngine([DeadlineMissMonitor(max_miss_rate=-1.0)])
    bundle = Obs(tracer=Tracer(), health=engine)
    loop = fl.RoundLoop(exp, _backend(exp), obs=bundle)
    hist = loop.run()
    assert all(r.get("incidents") == 1 for r in hist)
    assert len(engine.incidents) == len(hist)
    p = engine.write_jsonl(str(tmp_path / "inc.jsonl"))
    rows = [json.loads(l) for l in open(p)]
    assert len(rows) == len(hist)
    assert all(r["schema"] == INCIDENT_SCHEMA for r in rows)
    assert all(r["kind"] == "deadline_slo" for r in rows)


def test_health_observation_does_not_perturb_history():
    """A health engine must be a pure observer: rows identical to a
    health-disabled run except for the ``incidents`` count key."""
    exp = _exp()
    base = fl.RoundLoop(exp, _backend(exp)).run()
    engine = HealthEngine(default_monitors())
    loop = fl.RoundLoop(exp, _backend(exp), obs=Obs(health=engine))
    watched = loop.run()
    assert len(base) == len(watched)
    for a, b in zip(base, watched):
        bb = {k: v for k, v in b.items() if k != "incidents"}
        assert a == bb
    # and a healthy run has NO incident keys at all — byte-identical rows
    assert all("incidents" not in r for r in watched)
    assert engine.incidents == []


def test_health_cli_flags_build_engine(tmp_path):
    import argparse
    ap = argparse.ArgumentParser()
    obs.add_obs_cli_args(ap)
    inc_p = str(tmp_path / "inc.jsonl")
    args = ap.parse_args(["--health", "--incidents-out", inc_p,
                          "--slo-deadline-miss-rate", "0.25"])
    sess = obs.session_from_args(args)
    try:
        assert sess.obs.health is not None
        slos = [m for m in sess.obs.health.monitors
                if isinstance(m, DeadlineMissMonitor)]
        assert slos and slos[0].max_miss_rate == 0.25
        # drivers inherit the engine through the ambient context
        exp = _exp()
        loop = fl.RoundLoop(exp, _backend(exp))
        assert loop.obs.health is sess.obs.health
        loop.run()
    finally:
        sess.finish(quiet=True)
    assert os.path.exists(inc_p)             # written even when empty


def test_incident_records_are_json_complete():
    i = Incident(kind="k", severity="warn", message="m", round=2, t_s=1.5,
                 data={"x": 1})
    d = i.to_dict()
    assert d["schema"] == INCIDENT_SCHEMA
    assert json.loads(json.dumps(d)) == d


# ------------------------------------------------------------ HTML renderer

def test_timeline_svg_renders_sim_lanes(tmp_path):
    rep = _bundle(tmp_path / "a.json", seed=3)
    svg = render_timeline_svg(rep.trace)
    assert svg.startswith("<svg") and "onu" in svg
    # wall lanes are excluded by design
    assert "wall" not in svg


def test_diff_html_is_self_contained(tmp_path):
    a = _bundle(tmp_path / "a.json", seed=3)
    b = _bundle(tmp_path / "b.json", seed=4)
    html = render_diff_html(diff_bundles(a, b), a, b)
    assert html.startswith("<!DOCTYPE html>")
    assert "<svg" in html and "hard diffs" in html
    # no external resources: a standalone artifact
    assert "src=" not in html and "href=" not in html


# --------------------------------------------------------- regression gate

def _mini_sweep(mbits=1690.624, us=100.0, acc=0.5):
    return {
        "upstream": [{"N": 48, "classical_mbits": mbits * 6,
                      "sfl_mbits": mbits, "saving_pct": 83.3,
                      "bench": "upstream"}],
        "kernels": [{"name": "agg", "us_per_call": us,
                     "derived": f"gbps={1000.0 / us:.1f}",
                     "bench": "kernels"}],
        "accuracy": [{"round": 0, "classical_acc": acc - 0.1,
                      "sfl_two_step_acc": acc, "bench": "accuracy"}],
    }


def test_regress_clean_when_identical(tmp_path):
    from benchmarks import regress
    findings = regress.compare(_mini_sweep(), _mini_sweep())
    assert findings == []


def test_regress_injected_accounting_regression_exits_nonzero(tmp_path):
    """Acceptance: a synthetic injected regression makes the gate fail."""
    from benchmarks import regress
    base_p = tmp_path / "base.json"
    cand_p = tmp_path / "cand.json"
    base_p.write_text(json.dumps(_mini_sweep()))
    cand = _mini_sweep(mbits=2000.0)           # accounting drift: hard fail
    cand_p.write_text(json.dumps(cand))
    html_p = str(tmp_path / "regress.html")
    rc = regress.main(["--baseline", str(base_p), "--candidate", str(cand_p),
                       "--html", html_p])
    assert rc == 1
    assert "hard regressions" in open(html_p).read()
    # while the identical sweep passes through the same CLI
    assert regress.main(["--baseline", str(base_p),
                         "--candidate", str(base_p)]) == 0


def test_regress_timing_is_warn_only_accuracy_drop_hard_fails():
    from benchmarks import regress
    base = _mini_sweep()
    # 5x slower kernel (and its derived gbps string): warnings, not failures
    slow = _mini_sweep(us=500.0)
    findings = regress.compare(base, slow)
    assert findings and all(f.status == "warn" for f in findings)
    # accuracy: small jitter passes, a real drop hard-fails
    assert regress.compare(base, _mini_sweep(acc=0.49)) == []
    drop = regress.compare(base, _mini_sweep(acc=0.3))
    assert drop and all(f.status == "fail" for f in drop)
    # improvement is never a regression
    assert regress.compare(base, _mini_sweep(acc=0.9)) == []


def test_regress_differing_dispatch_stamps_are_incomparable():
    """A chip 'compiled' kernel row is never timed against a CPU 'ref'
    row; a row without the stamp (an older artifact) is still compared."""
    from benchmarks import regress
    base, cand = _mini_sweep(), _mini_sweep(us=5.0)
    base["kernels"][0].update(dispatch="ref", device="cpu:cpu")
    cand["kernels"][0].update(dispatch="compiled", device="tpu:TPU v5 lite")
    findings = regress.compare(base, cand)
    assert sorted((f.metric, f.status) for f in findings) == [
        ("device", "incomparable"), ("dispatch", "incomparable")]
    del base["kernels"][0]["dispatch"], base["kernels"][0]["device"]
    findings = regress.compare(base, cand)
    assert findings and all(f.status == "warn" for f in findings)
    assert {f.metric for f in findings} == {"us_per_call", "derived"}


def test_regress_missing_rows_and_benches_are_findings():
    from benchmarks import regress
    cand = _mini_sweep()
    del cand["kernels"]
    cand["upstream"][0]["N"] = 128             # re-keyed row
    findings = regress.compare(_mini_sweep(), cand)
    stats = {f.status for f in findings}
    assert "missing" in stats
    assert sum(1 for f in findings if f.status == "missing") >= 3


def test_regress_against_committed_baseline():
    """The committed BENCH_PR<n>.json compares clean against itself and
    regress auto-discovers the newest one."""
    from benchmarks import regress
    latest = regress.latest_baseline(REPO)
    assert latest is not None
    with open(latest) as f:
        sweep = json.load(f)
    assert regress.compare(sweep, sweep) == []


def test_bench_pr7_baseline_matches_current_schema():
    p = os.path.join(REPO, "BENCH_PR7.json")
    assert os.path.exists(p), "commit BENCH_PR7.json (benchmarks.run --json)"
    from benchmarks import report
    with open(p) as f:
        sweep = json.load(f)
    report.assert_schema(sweep)
    assert set(sweep) >= {"upstream", "involved", "dba", "hierarchy",
                          "kernels", "accuracy", "time_to_accuracy"}


# ----------------------------------------------------------- freeze_tables

def test_freeze_tables_emits_schema_stamped_rows(tmp_path, monkeypatch):
    (tmp_path / "results" / "dryrun").mkdir(parents=True)
    cell = {"arch": "qwen2-0.5b", "shape": "smoke", "mesh": "single",
            "mode": "sfl", "compile_s": 1.2,
            "memory": {"argument_gb": 0.5, "temp_gb": 0.25},
            "roofline": {"compute_s": 0.1, "memory_s": 0.2,
                         "collective_s": 0.05, "dominant": "memory",
                         "coll_pod_bytes": 1e9, "coll_ici_bytes": 0.0},
            "useful_ratio": 0.8}
    with open(tmp_path / "results" / "dryrun" / "cell.json", "w") as f:
        json.dump(cell, f)
    monkeypatch.chdir(tmp_path)
    from benchmarks import freeze_tables, report
    rows = freeze_tables.main(["--json", str(tmp_path / "frozen.json")])
    assert len(rows) == 1
    report.assert_schema({"freeze_tables": rows})
    assert rows[0]["bench_schema"] == report.BENCH_SCHEMA
    assert rows[0]["arch"] == "qwen2-0.5b"
    assert (tmp_path / "results" / "tables.md").exists()
    frozen = json.load(open(tmp_path / "frozen.json"))
    assert list(frozen) == ["freeze_tables"]
