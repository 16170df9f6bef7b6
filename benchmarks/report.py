"""Benchmark row emission.

:func:`emit_rows` / :func:`attach_schema` are the ONE stdout-CSV +
optional-JSON emission path shared by every bench main (bench_accuracy /
bench_dba / bench_hierarchy / bench_time_to_accuracy used to copy-paste
it). Every row is stamped with the uniform bench schema tag plus the
``repro.obs`` metrics schema, so all ``BENCH_*.json`` artifacts are
mechanically comparable across PRs (see ROADMAP: bench-snapshot
convention).
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional, Sequence, Tuple

BENCH_SCHEMA = "repro.bench/v1"


def attach_schema(rows: List[Dict], bench: str) -> List[Dict]:
    """Stamp each row with the bench name + uniform schema tags (copies —
    callers' row dicts are not mutated)."""
    from repro.obs import SCHEMA as OBS_SCHEMA
    out = []
    for r in rows:
        r = dict(r)
        r.setdefault("bench", bench)
        r.setdefault("bench_schema", BENCH_SCHEMA)
        r.setdefault("obs_schema", OBS_SCHEMA)
        out.append(r)
    return out


def _fmt_cell(v, spec: str) -> str:
    if v is None:
        return ""
    if spec:
        return format(v, spec)
    return str(v)


def emit_rows(rows: List[Dict], bench: str,
              columns: Sequence[Tuple[str, str]],
              header: Optional[str] = None,
              json_out: Optional[str] = None) -> List[Dict]:
    """Shared bench emission: schema-stamp → stdout CSV → optional JSON.

    ``columns`` is ``[(key, format_spec), ...]`` (empty spec → ``str``);
    returns the stamped rows so bench mains hand run.py schema-carrying
    records. ``json_out`` writes ``{bench: rows}`` exactly like the old
    per-bench ``--json`` blocks did.
    """
    rows = attach_schema(rows, bench)
    if header:
        print(header)
    print(",".join(k for k, _ in columns))
    for r in rows:
        print(",".join(_fmt_cell(r.get(k), spec) for k, spec in columns))
    if json_out:
        with open(json_out, "w") as f:
            json.dump({bench: rows}, f, indent=2, default=float)
        print(f"[json] wrote {len(rows)} rows to {json_out}")
    return rows


def assert_schema(rows_by_bench: Dict[str, List[Dict]]) -> None:
    """Every collected row must carry the uniform schema tags (the CI
    bench-smoke gate)."""
    for bench, rows in rows_by_bench.items():
        for i, r in enumerate(rows):
            missing = [k for k in ("bench", "bench_schema", "obs_schema")
                       if k not in r]
            if missing:
                raise AssertionError(
                    f"bench {bench!r} row {i} missing schema keys {missing} "
                    "— emit rows through benchmarks.report.emit_rows")

