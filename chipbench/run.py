"""Run one benchmark cell on the accelerator and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names the cell's
configuration and traffic; the configuration's file (``configs/``) names
its driver (``drivers/<driver>.py``); the traffic mix is
``traffic/<traffic>.json``; each per-layer metric is read by
``metrics/<name>.py``; the operations and bytes of a round come from
``counts/<model>.py``; the chip's peaks from ``peaks.json``. A new cell is
new files and one entry in ``BENCHMARK.json``.

A run: set-up (data and weights from ``--seed``, the program's objects, its
first rounds, which compile, and which the output check follows), then
whole rounds until ``--seconds`` have passed, then the peak device memory,
then the program's state is freed and the plain reference checks the first
rounds. With ``--trace 1`` the window runs under the profiler and the
per-layer metrics are read from its trace. The last line of stdout is one
JSON object; the numbers compared, each with its limit, are also the last
lines of stderr. With no TPU, too few chips, or no program beside this
directory, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()   # set-up counts from here: before any heavy import

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
HERE = "chipbench"
# the persistent compilation cache: a fixed path inside the checkout, since
# the path is part of the cache's key
CACHE_DIR = Path("results") / "chipbench" / "jax_cache"
TRACE_DIR = Path("results") / "chipbench" / "trace"
# the traced window: whole rounds until this many seconds have passed (or
# --seconds, if shorter); a trace of the full window would be large
TRACE_SECONDS = 8.0


class Failure(Exception):
    """The run cannot give a result."""


def load_module(path: Path):
    """Import a plugin file (driver, metric, counts) found by name."""
    if not path.is_file():
        raise Failure(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_plugin_{path.parent.name}_{path.stem}".replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its files, read from ``root``."""

    def __init__(self, root: Path, name: str):
        self.root = root
        self.bench = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise Failure(f"no workload {name!r} in BENCHMARK.json; "
                          f"known: {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = json.loads(
            (root / self.config_entry["file"]).read_text())
        self.traffic = json.loads(
            (root / HERE / "traffic" / f"{self.entry['traffic']}.json"
             ).read_text())
        self.chips = int(self.entry["chips"])

    def end_to_end(self) -> List[dict]:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[dict]:
        reported = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if self.name in m.get("workloads", [self.name])
                and m["moves"] in reported]

    def plugin(self, kind: str, name: str):
        return load_module(self.root / HERE / kind / f"{name}.py")

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.root / HERE / "peaks.json").read_text())
        if device_kind not in table["devices"]:
            raise Failure(f"no peaks for device kind {device_kind!r} in "
                          f"peaks.json (known: {sorted(table['devices'])})")
        return table["devices"][device_kind]


class CompileCounter:
    """Counts compiles and persistent-cache reads through jax.monitoring."""

    def __init__(self):
        self.n = {"backend_compiles": 0, "cache_hits": 0, "cache_misses": 0}

    def install(self) -> "CompileCounter":
        import jax

        def on_event(event: str, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.n["cache_hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.n["cache_misses"] += 1

        def on_duration(event: str, _secs: float, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n["backend_compiles"] += 1

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        return self

    def snapshot(self) -> Dict[str, int]:
        return dict(self.n)


def device_info(devices) -> dict:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def peak_memory(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             counter: Optional[CompileCounter] = None,
             t_start: Optional[float] = None, log=print) -> Dict[str, Any]:
    """Set up, measure, check; returns the result object."""
    import jax

    t_start = T_START if t_start is None else t_start
    counter = counter or CompileCounter()
    driver = cell.plugin("drivers", cell.config["driver"])
    session = driver.Session(cell, seed, devices)
    session.warm_up()
    setup_s = time.time() - t_start
    n_setup = counter.snapshot()
    log(f"# set-up {setup_s:.3f} s; compiles and cache reads in set-up: "
        f"{n_setup}")

    limit = min(seconds, TRACE_SECONDS) if trace else seconds
    tracedir = cell.root / TRACE_DIR / f"{cell.name}-{seed}"
    if trace:
        import shutil
        shutil.rmtree(tracedir, ignore_errors=True)
        jax.profiler.start_trace(str(tracedir))
    rounds: List[dict] = []
    rnd = session.first_round
    t0 = time.perf_counter()
    try:
        while not rounds or time.perf_counter() - t0 < limit:
            t = time.perf_counter()
            with jax.profiler.StepTraceAnnotation("chipbench.round",
                                                  step_num=rnd):
                rec = session.round(rnd)
            rec["round_s"] = time.perf_counter() - t
            rounds.append(rec)
            rnd += 1
        window_s = time.perf_counter() - t0
    finally:
        if trace:
            jax.profiler.stop_trace()
    in_window = {k: v - n_setup[k] for k, v in counter.snapshot().items()}
    log(f"# window: {len(rounds)} rounds in {window_s:.3f} s; compiles "
        f"and cache reads inside the window: {in_window}")
    memory = peak_memory(devices)

    metrics: Dict[str, dict] = {}
    device = device_info(devices)
    device["memory_peak_bytes"] = memory
    breakdown = None
    if trace:
        metrics, device_extra, breakdown = read_trace(
            cell, session, tracedir, rounds, devices, log)
        device.update(device_extra)
    else:
        values = {"round_s": window_s / len(rounds), "setup_s": setup_s,
                  "peak_hbm_gib": (memory / 2 ** 30 if memory is not None
                                   else None)}
        for m in cell.end_to_end():
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}

    session.free()
    gc.collect()
    t = time.perf_counter()
    checks = session.check()
    log(f"# output check: {time.perf_counter() - t:.3f} s")
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result: Dict[str, Any] = {
        "correct": correct, "attempted": len(rounds), "failed": 0,
        "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def read_trace(cell: Cell, session, tracedir: Path, rounds, devices, log):
    """Per-layer metrics, ``busy_s``/``window_s`` and the breakdown."""
    from chipbench import trace as tr

    t = time.perf_counter()
    data = tr.load(tr.find(tracedir))
    # on an accelerator every device metric has something to read: a trace
    # with no op line, or a metric that reads nothing, is a broken run
    strict = devices[0].platform != "cpu"
    if strict and not data.devices:
        raise Failure(f"no device plane in the trace has a {tr.OPS_LINE!r} "
                      f"line with events")
    window = data.window()
    extra: Dict[str, Any] = {}
    breakdown = None
    if window is not None and data.devices:
        lo, hi = window
        busy = [tr.length(tr.clip(data.busy(d), lo, hi))
                for d in data.devices]
        extra = {"busy_s": statistics.fmean(busy) / 1e9,
                 "window_s": (hi - lo) / 1e9}
        breakdown = {"device_ops": [list(x) for x in tr.top_ops(data)],
                     "idle_gaps": [list(x) for x in tr.top_gaps(data)]}
    ctx = {"trace": data, "rounds": rounds, "config": cell.config,
           "traffic": session.traffic,
           "chips": len(devices),
           "peaks": cell.peaks(devices[0].device_kind) if data.devices
           else None,
           "counts": cell.plugin("counts", cell.config["model"])}
    metrics = {}
    for m in cell.per_layer():
        value = cell.plugin("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    missing = [m["name"] for m in cell.per_layer() if m["name"] not in metrics]
    if strict and missing:
        raise Failure(f"the trace gave nothing to read for {missing}")
    log(f"# trace read in {time.perf_counter() - t:.3f} s: "
        f"{len(data.devices)} devices, {len(data.rounds())} rounds")
    return metrics, extra, breakdown


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "BENCHMARK.json").is_file() or not (
            ROOT / "src" / "repro").is_dir():
        print(f"chipbench: FAIL no BENCHMARK.json or no program (src/repro) "
              f"under {ROOT}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # libtpu would otherwise log to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        cell = Cell(ROOT, args.workload)
        import jax

        devices = jax.devices()
        if devices[0].platform != "tpu":
            raise Failure(f"needs a TPU, JAX found {devices[0].platform}")
        if len(devices) < cell.chips:
            raise Failure(f"{args.workload} needs {cell.chips} chips, JAX "
                          f"found {len(devices)}")
        cell.peaks(devices[0].device_kind)      # an unknown chip fails here
        jax.config.update("jax_compilation_cache_dir", str(ROOT / CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        counter = CompileCounter().install()
        log = lambda *a: print(*a, flush=True)
        log(f"# device {device_info(devices[:cell.chips])}; compile cache "
            f"{ROOT / CACHE_DIR}")
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devices[:cell.chips], counter=counter, log=log)
    except Failure as e:
        print(f"chipbench: FAIL {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']:.6g} (limit {c['limit']:g})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
