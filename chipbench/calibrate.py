"""Read the output check's numbers over many seeds, for setting its limits.

    python3 chipbench/calibrate.py --workload <cell> --seeds 11 12 13 ...

For each seed, in one process: the program's first rounds as a run's
set-up drives them, then the plain reference, then the control (the
reference in the next precision below the configuration's, put in the
program's place), each compared with the reference by the cell's own
comparison; and the planted faults the reference computes on its first
round. One JSON line per seed goes to stdout and to
``results/chipbench/calibrate-<cell>.jsonl``. A run of the benchmark never calls
this; the limits in the configuration file come from its readings
(PERF.md gives them).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from chipbench import run

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"calibrate: FAIL needs a TPU, JAX found "
              f"{devices[0].platform}", file=sys.stderr)
        return 1
    jax.config.update("jax_compilation_cache_dir", str(ROOT / run.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = run.Cell(ROOT, args.workload)
    driver = cell.plugin("drivers", cell.config["driver"])
    out = ROOT / "results" / "chipbench" / f"calibrate-{args.workload}.jsonl"
    out.parent.mkdir(parents=True, exist_ok=True)
    for seed in args.seeds:
        t = time.perf_counter()
        session = driver.Session(cell, seed, devices[:cell.chips])
        session.warm_up()
        session.free()
        gc.collect()
        row = {"seed": seed, **session.calibrate()}
        row["seconds"] = time.perf_counter() - t
        line = json.dumps(row)
        print(line, flush=True)
        with out.open("a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
