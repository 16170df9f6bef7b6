"""A copy of the benchmark tree with one tiny OLMo cell, for CPU tests.

The tiny cell runs the same driver (``drivers/gradient.py``), reference
and comparison as ``olmo1b_sfl_fsdp4``, at widths and a population that a
test can hold: OLMo at 2 layers, d 64, 4 heads, a 256-token vocabulary,
2 ONUs x 4 clients, 4 rows x 32 tokens on a (1, 1) mesh. Its limits are
the real cell's.
"""
from __future__ import annotations

import json
from pathlib import Path

from chipbench_tiny import ROOT, SEED, make_tree

WIDTHS = {"n_layers": 2, "d_model": 64, "n_heads": 4, "mlp_ratio": 8,
          "vocab_size": 256, "embedding_size": 256, "rope_theta": 10000,
          "layer_norm_eps": 1e-05}
CELL = "olmo1b_sfl_fsdp4"


def make_olmo_tree(tmp: Path, name: str = "tiny_olmo") -> Path:
    """``make_tree`` with one more cell named ``name``: the OLMo cell's
    files at the tiny sizes."""
    make_tree(tmp)
    cfg = json.loads((ROOT / "chipbench/configs/olmo_1b.json").read_text())
    cfg["widths"].update(WIDTHS)
    cfg["deployment"].update(n_onus=2, clients_per_onu=4)
    (tmp / f"chipbench/configs/{name}.json").write_text(json.dumps(cfg))
    tf = json.loads(
        (ROOT / "chipbench/traffic/fedsgd_r32_s2048.json").read_text())
    tf.update(n_selected=4, rows=4, seq=32, mesh=[1, 1])
    (tmp / f"chipbench/traffic/{name}.json").write_text(json.dumps(tf))
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    base = next(w for w in bench["workloads"] if w["name"] == CELL)
    bench["configs"].append(dict(
        next(c for c in bench["configs"] if c["name"] == base["config"]),
        name=name, file=f"chipbench/configs/{name}.json"))
    bench["workloads"].append(dict(base, name=name, config=name,
                                   traffic=name, chips=1))
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(name)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp


def run(root: Path, name: str = "tiny_olmo", trace: bool = False,
        seed: int = SEED) -> dict:
    import jax

    from chipbench import run as harness
    return harness.run_cell(harness.Cell(root, name), seed, 0.05, trace,
                            jax.devices()[:1], log=lambda *a: None)
