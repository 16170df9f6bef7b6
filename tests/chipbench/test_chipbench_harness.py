"""The chip benchmark's harness on the CPU: what it finds, what it refuses,
and that ``BENCHMARK.json`` keeps to its own contract."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import textwrap

import pytest

from chipbench_tiny import ROOT, SEED, make_tree, run

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_new_cell_is_found_from_new_files_alone(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and one entry in
    BENCHMARK.json make a cell: no file that was there is edited."""
    root = make_tree(tmp_path, "fresh")
    before = {p: p.read_bytes() for p in (tmp_path / "chipbench").rglob("*")
              if p.is_file()}
    (tmp_path / "chipbench/metrics/rounds_seen.py").write_text(
        textwrap.dedent('''
            def read(ctx):
                return float(len(ctx["trace"].rounds())) or None
        '''))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "rounds_seen", "unit": "rounds", "better": "higher",
        "source": "program_span", "layer": "driver", "moves": "round_s",
        "workloads": ["fresh"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    timed = run(root, "fresh")
    assert timed["correct"] is True, timed["checks"]
    assert set(timed["metrics"]) == {"round_s", "setup_s"}   # CPU: no HBM
    traced = run(root, "fresh", trace=True)
    assert traced["correct"] is True
    assert traced["metrics"]["rounds_seen"]["value"] >= 1
    assert traced["metrics"]["train_s.round"]["value"] > 0
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_exits_nonzero_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "cnn_sfl_n128",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_cpu_env(), capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_exits_nonzero_alone_in_a_directory(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", "cnn_sfl_n128",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_cpu_env(), capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_unknown_device_kind_is_an_error():
    from chipbench import run as harness
    cell = harness.Cell(ROOT, "cnn_sfl_n128")
    assert cell.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.Failure, match="no peaks"):
        cell.peaks("TPU v9 imaginary")


def test_each_metric_moves_what_its_cells_report():
    reports = {w["name"]: {m["name"] for m in BENCH["end_to_end"]
                           if w["name"] in m.get("workloads", [w["name"]])}
               for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        cells = m.get("workloads", list(reports))
        assert cells, m["name"]
        for cell in cells:
            assert m["moves"] in reports[cell], (m["name"], cell)
    for cell, names in reports.items():
        assert "setup_s" in names and len(names) >= 2, cell
        assert any(cell in m.get("workloads", [cell])
                   for m in BENCH["per_layer"]), cell


def test_benchmark_file_keeps_its_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[k]}) == len(BENCH[k])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (ROOT / "chipbench/traffic" / f"{w['traffic']}.json").is_file()
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert (ROOT / "chipbench/metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
