"""chip_smoke.py: it refuses to run without a TPU (or without the repo
beside it), and its phases' logic holds at a tiny size on the CPU."""
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro import configs

REPO = os.path.join(os.path.dirname(__file__), "..")
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _smoke_module():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("alone", [False, True])
def test_chip_smoke_fails_without_a_chip(tmp_path, alone):
    script = SCRIPT
    if alone:  # a directory that holds chip_smoke.py and nothing else
        script = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = subprocess.run([sys.executable, str(script)], capture_output=True,
                       text=True, cwd=tmp_path, timeout=120,
                       env={"PATH": os.environ.get("PATH", ""),
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "FAIL" in r.stderr


def test_chip_smoke_fl_phase_on_cpu():
    """The one-chip phase's runs and checks, at the reduced CNN and N=16;
    the CPU check compares the CPU with itself (error 0)."""
    smoke = _smoke_module()
    out = smoke.fl_phase(jax.devices()[0],
                         model_cfg=configs.get("femnist_cnn").reduced(),
                         n_selected=16,
                         runs=(("sfl_two_step", "none", 2),
                               ("classical", "none", 1),
                               ("sfl_two_step", "int8", 1)))
    assert [r["run"] for r in out["runs"]] == [
        "sfl_two_step/none", "classical/none", "sfl_two_step/int8"]
    assert out["cpu_check"] == {"highest/H8": 0.0, "default/H1": 0.0}
    tol = {f"{p}/H{h}": rtol for p, h, rtol in smoke.CPU_CHECKS}
    assert all(out["cpu_control"][k] > tol[k] for k in tol)


def test_chip_smoke_mesh_phase_on_cpu():
    """The four-chip phase's sfl-vs-classical comparison on the one CPU
    device, at the reduced qwen2-0.5b: the two schedules are the same
    program there, and the one-step-short control is caught."""
    smoke = _smoke_module()
    out = smoke.mesh_phase(model_cfg=configs.get_smoke("qwen2-0.5b"),
                           batch=4, seq=16, steps=2)
    assert out["sfl"]["losses"] == out["classical"]["losses"]
    assert out["param_rel"] == 0.0
    assert out["param_control"] > smoke.PARAM_RTOL
