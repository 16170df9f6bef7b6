"""Benchmark harness — one bench per paper table/figure + kernel timing.

``python -m benchmarks.run [--full] [--only NAME]`` prints
``name,us_per_call,derived``-style CSV blocks per bench:
  upstream  — Fig. 2a (upstream Mb per round vs N)
  involved  — Fig. 2b (involved clients under the 25 s deadline)
  accuracy  — Fig. 2c (FedAvg accuracy, any registered repro.fl strategy)
  dba       — DBA policy × wavelengths × background-load sweep (beyond-paper)
  hierarchy — multi-PON forest: per-segment Mbits vs n_pons ×
              {hier_sfl, sfl, classical} (beyond-paper, DESIGN.md §12)
  scale     — population-scale engine sweep: sim wall-time vs ONU count,
              fast vs event engine parity + trunk flatness (DESIGN.md §15)
  time_to_accuracy — simulated-seconds-to-target, sync vs semi_sync vs
              fedbuff through the repro.runtime Orchestrator (beyond-paper)
  pareto    — bandwidth–accuracy Pareto: {none,int8,int4,topk} wire
              compression × {sfl,hier_sfl,classical} (DESIGN.md §17)
  kernels   — ONU-AF / quantize / top-k micro-bench
  report    — EXPERIMENTS tables from results/dryrun/*.json (if present)

``--json OUT.json`` additionally writes every bench's rows as
machine-readable JSON ({bench: [row, ...]}) so the perf/accuracy
trajectory is trackable across PRs; ``--rounds R`` overrides the accuracy
bench's round count.
"""
from __future__ import annotations

import argparse
import json
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="upstream|involved|accuracy|dba|hierarchy|scale|"
                         "time_to_accuracy|pareto|kernels|report")
    ap.add_argument("--full", action="store_true",
                    help="accuracy bench with the full LEAF CNN (slow)")
    ap.add_argument("--rounds", type=int, default=None,
                    help="accuracy bench rounds")
    ap.add_argument("--json", default=None, metavar="OUT.json",
                    help="write per-bench rows as JSON")
    ap.add_argument("--trace-out", default=None, metavar="TRACE.json",
                    help="one merged Chrome/Perfetto trace for the whole "
                         "sweep (every bench's drivers share the session "
                         "tracer)")
    ap.add_argument("--metrics-out", default=None, metavar="METRICS.jsonl",
                    help="one merged MetricsRegistry artifact for the whole "
                         "sweep (per-driver registries folded at exit)")
    args = ap.parse_args()

    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro import obs
    sess = obs.session(trace_out=args.trace_out,
                       metrics_out=args.metrics_out, driver="bench_sweep")

    from benchmarks import (bench_accuracy, bench_dba, bench_hierarchy,
                            bench_involved, bench_kernels, bench_pareto,
                            bench_scale, bench_time_to_accuracy,
                            bench_upstream, report)

    acc_argv = []
    tta_argv = []
    hier_argv = []
    # small selection keeps the 12-cell sweep CI-sized; seeded, so the
    # rows stay deterministic for regress.py's accounting gate
    pareto_argv = ["--n-selected", "16"]
    if args.rounds is not None:
        acc_argv += ["--rounds", str(args.rounds)]
        tta_argv += ["--rounds", str(args.rounds)]
        hier_argv += ["--rounds", str(args.rounds)]
        pareto_argv += ["--rounds", str(args.rounds)]
    if args.full:
        acc_argv += ["--full"]
    # fast-engine only: the sweep reaches 1e5 clients, and the same argv
    # is used by the CI scale-smoke step so BENCH_*.json rows always align
    scale_argv = ["--sim-engine", "fast"]

    benches = {
        "upstream": lambda: bench_upstream.main([]),
        "involved": lambda: bench_involved.main([]),
        "dba": lambda: bench_dba.main([]),
        "hierarchy": lambda: bench_hierarchy.main(hier_argv),
        "scale": lambda: bench_scale.main(scale_argv),
        "kernels": bench_kernels.main,
        "accuracy": lambda: bench_accuracy.main(acc_argv),
        "time_to_accuracy": lambda: bench_time_to_accuracy.main(tta_argv),
        "pareto": lambda: bench_pareto.main(pareto_argv),
    }
    names = [args.only] if args.only else list(benches)
    collected = {}
    for name in names:
        if name == "report":
            report.main()
            continue
        t0 = time.time()
        print(f"\n=== {name} ===")
        rows = benches[name]()
        if rows is not None:
            collected[name] = rows
        print(f"=== {name} done in {time.time()-t0:.1f}s ===")
    # every bench emits through report.emit_rows — enforce the uniform
    # schema before anything lands in a BENCH_*.json artifact
    report.assert_schema(collected)
    sess.finish()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(collected, f, indent=2, default=float)
        print(f"[json] wrote {sum(len(v) for v in collected.values())} rows "
              f"({', '.join(collected)}) to {args.json}")


if __name__ == "__main__":
    main()
