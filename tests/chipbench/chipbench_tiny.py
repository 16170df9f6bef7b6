"""A copy of the benchmark tree with one tiny cell, for CPU tests.

The tiny cell runs the same driver, reference and comparison as
``cnn_sfl_n128``, at widths and a population that a test can hold: the
LEAF CNN at 4/8 channels and a 32-wide dense layer, 2 ONUs x 4 clients,
N=6, H=2, chunks of 4. Its limits are the real cell's.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

SEED = 3_000_000_001      # past 32 bits, as the benchmark's seeds may be


def make_tree(tmp: Path, name: str = "tiny") -> Path:
    """``tmp`` with BENCHMARK.json and a copy of ``chipbench/`` holding a
    new configuration, traffic mix and cell named ``name``."""
    shutil.copytree(ROOT / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((ROOT / "chipbench/configs/femnist_cnn.json").read_text())
    cfg["widths"] = {"cnn_channels": [4, 8], "cnn_fc": 32}
    cfg["deployment"].update(n_onus=2, clients_per_onu=4)
    (tmp / f"chipbench/configs/{name}_cnn.json").write_text(json.dumps(cfg))
    tf = json.loads((ROOT / "chipbench/traffic/sfl_n128.json").read_text())
    tf.update(n_selected=6, local_steps=2, client_chunk=4)
    (tmp / f"chipbench/traffic/{name}.json").write_text(json.dumps(tf))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    base = bench["workloads"][0]
    bench["configs"].append(dict(bench["configs"][0], name=f"{name}_cnn",
                                 file=f"chipbench/configs/{name}_cnn.json"))
    bench["workloads"].append(dict(base, name=name, config=f"{name}_cnn",
                                   traffic=name))
    for m in bench["per_layer"]:
        if base["name"] in m.get("workloads", []):
            m["workloads"].append(name)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp


def run(root: Path, name: str = "tiny", trace: bool = False,
        seed: int = SEED) -> dict:
    import jax

    from chipbench import run as harness
    return harness.run_cell(harness.Cell(root, name), seed, 0.05, trace,
                            jax.devices()[:1], log=lambda *a: None)


def load_module(path: Path):
    from chipbench.run import load_module as load
    return load(path)
