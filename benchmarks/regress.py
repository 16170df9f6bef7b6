"""Bench regression gate — compare two BENCH_*.json sweeps under policy.

``python -m benchmarks.regress --baseline BENCH_PR6.json --candidate NEW.json``
aligns the two artifacts bench by bench (rows keyed on each bench's
natural axis — N, (dba, wavelengths, bg_load), (n_pons, mode), round,
(policy, mode), kernel name) and classifies every metric delta:

  * **accounting** (``*_mbits``, ``*_involved``, ``*_frac``,
    ``saving_pct``, counts, staleness) — the deterministic simulator's
    outputs; any drift beyond float tolerance is a HARD regression.
  * **accuracy** (``*acc*``) — hard regression only when the candidate
    falls more than ``--acc-drop`` below the baseline (improvement and
    jitter above are fine).
  * **timing** (``us_per_call``, ``wall_s``, ``*_s`` budgets measured on
    the host) — WARN-only; CI machines are noisy and host time is not a
    simulator property.
  * **incomparable** — both rows stamp the kernel dispatch mode or device
    and the stamps differ (a TPU ``compiled`` row against a CPU ``ref``
    row): one finding per differing stamp, and the row's timings are not
    compared. A row without the stamp (an older artifact) is compared.

Exit code 0 = clean (warnings allowed), 1 = hard regressions — the CI
gate (.github/workflows) runs this at smoke settings against the
committed ``BENCH_PR<n>.json`` baseline and uploads the HTML report.
The tolerance machinery is `repro.obs.audit.diff`'s; this module adds
the bench-axis alignment and the metric policy.
"""
from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

# row-alignment key per bench (each bench's natural sweep axis)
ALIGN_KEYS: Dict[str, Tuple[str, ...]] = {
    "upstream": ("N",),
    "involved": ("N",),
    "dba": ("dba", "wavelengths", "bg_load"),
    "hierarchy": ("n_pons", "mode"),
    "accuracy": ("round",),
    "time_to_accuracy": ("policy", "mode"),
    "kernels": ("name",),
    "scale": ("engine", "mode", "n_clients"),
    "pareto": ("mode", "compress"),
}

_SKIP_FIELDS = {"bench", "bench_schema", "obs_schema"}
# what ran a timed row (kernel rows): timings differ in kind across them
_STAMP_FIELDS = ("dispatch", "device")
# host-measured time: never a hard failure
_TIMING_PAT = re.compile(r"(us_per_call|wall_s|^t_to_target_s$|compile_s)")
_ACC_PAT = re.compile(r"acc")


class Finding:
    """One metric delta with its policy classification."""

    def __init__(self, bench: str, key: str, metric: str, base: Any,
                 cand: Any, status: str, note: str = ""):
        self.bench = bench
        self.key = key
        self.metric = metric
        self.base = base
        self.cand = cand
        self.status = status  # "fail" | "warn" | "missing" | "incomparable"
        self.note = note

    def line(self) -> str:
        tag = {"fail": "FAIL", "warn": "warn", "missing": "MISS",
               "incomparable": "INCOMPARABLE"}[self.status]
        s = (f"[{tag}] {self.bench}{self.key}.{self.metric}: "
             f"{self.base!r} -> {self.cand!r}")
        if self.note:
            s += f"  — {self.note}"
        return s


def _is_num(v: Any) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _close(a: float, b: float, rtol: float) -> bool:
    if math.isnan(a) and math.isnan(b):
        return True
    if math.isinf(a) or math.isinf(b):
        return a == b
    return abs(a - b) <= rtol * max(abs(a), abs(b), 1e-12)


def classify(metric: str, base: Any, cand: Any, rtol: float,
             acc_drop: float) -> Optional[str]:
    """None = within policy; else "fail"/"warn"."""
    if metric == "derived":
        # kernel "derived" strings embed measured gbps — host noise
        return None if base == cand else "warn"
    if not (_is_num(base) and _is_num(cand)):
        return None if base == cand else "fail"
    b, c = float(base), float(cand)
    if _TIMING_PAT.search(metric):
        # warn only on gross movement (2x either way) — host noise
        if b > 0 and c > 0 and (c > 2.0 * b or c < 0.5 * b):
            return "warn"
        return None
    if _ACC_PAT.search(metric):
        return "fail" if c < b - acc_drop else None
    # accounting: deterministic simulator output, float tolerance only
    return None if _close(b, c, rtol) else "fail"


def _row_key(bench: str, row: Dict[str, Any]) -> Tuple:
    keys = ALIGN_KEYS.get(bench)
    if keys is None:
        return ()
    return tuple(row.get(k) for k in keys)


def compare(baseline: Dict[str, List[Dict]], candidate: Dict[str, List[Dict]],
            rtol: float = 1e-6, acc_drop: float = 0.02,
            benches: Optional[Sequence[str]] = None) -> List[Finding]:
    """Align and classify; returns every out-of-policy finding."""
    findings: List[Finding] = []
    names = benches if benches is not None else sorted(set(baseline)
                                                      | set(candidate))
    for bench in names:
        rb, rc = baseline.get(bench), candidate.get(bench)
        if rb is None or rc is None:
            side = "candidate" if rb is not None else "baseline"
            findings.append(Finding(bench, "", "(bench)", bool(rb), bool(rc),
                                    "missing", f"absent from {side}"))
            continue
        ib = {_row_key(bench, r): r for r in rb}
        ic = {_row_key(bench, r): r for r in rc}
        for key in ib:
            if key not in ic:
                findings.append(Finding(bench, f"{key}", "(row)", "present",
                                        None, "missing",
                                        "row absent from candidate"))
        for key, row_c in ic.items():
            row_b = ib.get(key)
            if row_b is None:
                findings.append(Finding(bench, f"{key}", "(row)", None,
                                        "present", "missing",
                                        "row absent from baseline"))
                continue
            stamps = [m for m in _STAMP_FIELDS if m in row_b and m in row_c
                      and row_b[m] != row_c[m]]
            for metric in stamps:
                findings.append(Finding(bench, f"{key}", metric, row_b[metric],
                                        row_c[metric], "incomparable",
                                        "timings not compared"))
            for metric in sorted(set(row_b) | set(row_c)):
                if (metric in _SKIP_FIELDS or metric in _STAMP_FIELDS
                        or metric in ALIGN_KEYS.get(bench, ())):
                    continue
                if stamps and (metric == "derived"
                               or _TIMING_PAT.search(metric)):
                    continue
                vb, vc = row_b.get(metric), row_c.get(metric)
                status = classify(metric, vb, vc, rtol, acc_drop)
                if status:
                    findings.append(Finding(bench, f"{key}", metric,
                                            vb, vc, status))
    return findings


def latest_baseline(repo_root: str = ".") -> Optional[str]:
    """The highest-numbered committed BENCH_PR<n>.json."""
    paths = glob.glob(os.path.join(repo_root, "BENCH_PR*.json"))
    def prnum(p):
        m = re.search(r"BENCH_PR(\d+)\.json$", p)
        return int(m.group(1)) if m else -1
    paths = [p for p in paths if prnum(p) >= 0]
    return max(paths, key=prnum) if paths else None


def _render_html(findings: List[Finding], baseline: str,
                 candidate: str) -> str:
    import html as _h
    rows = ["<table><tr><th>status</th><th>bench</th><th>row</th>"
            "<th>metric</th><th>baseline</th><th>candidate</th>"
            "<th>note</th></tr>"]
    for f in findings:
        cls = {"fail": "diff", "warn": "warn", "missing": "missing_a",
               "incomparable": "warn"}
        rows.append(f'<tr class="{cls[f.status]}"><td>{f.status}</td>'
                    f"<td>{_h.escape(f.bench)}</td>"
                    f"<td>{_h.escape(str(f.key))}</td>"
                    f"<td>{_h.escape(f.metric)}</td>"
                    f"<td>{_h.escape(str(f.base))}</td>"
                    f"<td>{_h.escape(str(f.cand))}</td>"
                    f"<td>{_h.escape(f.note)}</td></tr>")
    rows.append("</table>")
    n_fail = sum(1 for f in findings if f.status in ("fail", "missing"))
    n_warn = sum(1 for f in findings if f.status == "warn")
    verdict = (f'<p class="bad">{n_fail} hard regressions, {n_warn} '
               "warnings</p>" if n_fail else
               f'<p class="ok">no hard regressions ({n_warn} warnings)</p>')
    from repro.obs.audit.html import _CSS
    return ("<!DOCTYPE html><html><head><meta charset='utf-8'>"
            "<title>bench regression report</title>"
            f"<style>{_CSS}</style></head><body>"
            "<h1>benchmarks.regress</h1>"
            f"<p>baseline: <code>{_h.escape(baseline)}</code><br>"
            f"candidate: <code>{_h.escape(candidate)}</code></p>"
            + verdict
            + ("".join(rows) if findings
               else '<p class="ok">all rows within policy</p>')
            + "</body></html>")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.regress",
        description="Compare a fresh bench sweep against the committed "
                    "baseline; exit 1 on hard regressions.")
    ap.add_argument("--baseline", default=None,
                    help="baseline BENCH_*.json (default: the latest "
                         "committed BENCH_PR<n>.json)")
    ap.add_argument("--candidate", required=True,
                    help="fresh `python -m benchmarks.run --json` artifact")
    ap.add_argument("--rtol", type=float, default=1e-6,
                    help="float tolerance for accounting metrics")
    ap.add_argument("--acc-drop", type=float, default=0.02,
                    help="allowed absolute accuracy drop before hard fail")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench subset")
    ap.add_argument("--html", default=None, metavar="REPORT.html",
                    help="write the regression report as standalone HTML")
    args = ap.parse_args(argv)

    base_path = args.baseline or latest_baseline()
    if base_path is None:
        print("no BENCH_PR<n>.json baseline found", file=sys.stderr)
        return 2
    with open(base_path) as f:
        baseline = json.load(f)
    with open(args.candidate) as f:
        candidate = json.load(f)
    benches = args.only.split(",") if args.only else None
    findings = compare(baseline, candidate, rtol=args.rtol,
                       acc_drop=args.acc_drop, benches=benches)
    n_fail = sum(1 for f in findings if f.status in ("fail", "missing"))
    n_incomparable = sum(1 for f in findings if f.status == "incomparable")
    n_warn = len(findings) - n_fail - n_incomparable
    print(f"baseline:  {base_path}")
    print(f"candidate: {args.candidate}")
    for f in findings:
        print(f.line())
    print(f"TOTAL: {n_fail} hard regressions, {n_warn} warnings, "
          f"{n_incomparable} incomparable")
    if args.html:
        with open(args.html, "w") as f:
            f.write(_render_html(findings, base_path, args.candidate))
        print(f"wrote {args.html}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
