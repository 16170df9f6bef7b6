"""Per-device collective bytes by mesh axis, read from compiled HLO text.

Each all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute is charged its ring traffic per device and split evenly
over the mesh axes its replica group spans (device id -> mesh coordinate).
A collective inside a loop body is counted once, and an all-reduce is
charged as one even where only a dynamic slice reads its result.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional

import numpy as np

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}
_COLL_RE = re.compile(
    r"%?([\w.-]+)\s*=\s*((?:\([^)]*\))|(?:\w+\[[\d,]*\][^\s]*))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(([^)]*)\)")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape: str) -> int:
    """Bytes of ``f32[16,128]{...}`` or of a tuple of such shapes."""
    total = 0
    for m in _SHAPE_RE.finditer(shape):
        if m.group(1) in _DTYPE_BYTES:
            dims = [int(d) for d in m.group(2).split(",") if d]
            total += int(np.prod(dims)) * _DTYPE_BYTES[m.group(1)]
    return total


def _replica_groups(line: str) -> Optional[List[List[int]]]:
    m = re.search(r"replica_groups=\{\{([^}]*(?:\},\{[^}]*)*)\}\}", line)
    if m:
        return [[int(x) for x in grp.split(",") if x.strip()]
                for grp in m.group(1).split("},{")]
    # iota form: replica_groups=[2,4]<=[2,2,2]T(1,0,2) or <=[8]
    m = re.search(
        r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", line)
    if m:
        dims = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims)))
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            ids = ids.reshape(dims).transpose(perm).reshape(-1)
        return ids.reshape(int(m.group(1)), int(m.group(2))).tolist()
    return None


def axis_bytes(txt: str, mesh) -> Dict[str, float]:
    """Per-device collective traffic in bytes, keyed by mesh axis name."""
    coords = {int(d.id): c for c, d in np.ndenumerate(mesh.devices)}
    axes = tuple(mesh.axis_names)
    shapes: Dict[str, str] = {}
    for m in re.finditer(r"%([\w.-]+) = (\([^)]*\)|\w+\[[\d,]*\]\S*)", txt):
        shapes.setdefault(m.group(1), m.group(2))
    out = {a: 0.0 for a in axes}
    for line in txt.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done" in line.split("=")[1][:40]:
            continue
        groups = _replica_groups(line)
        if groups is None or len(groups[0]) <= 1:
            continue
        g = len(groups[0])
        varying = {ax for dev in groups[0][1:]
                   for ax, a, b in zip(axes, coords[groups[0][0]], coords[dev])
                   if a != b}
        op_bytes = 0
        for op in m.group(4).split(","):
            name = op.strip().lstrip("%").split(" ")[0]
            op_bytes += _shape_bytes(shapes.get(name, op))
        kind, factor = m.group(3), (g - 1) / g
        if kind == "all-reduce":
            traffic = 2.0 * op_bytes * factor
        elif kind == "all-gather":
            traffic = _shape_bytes(m.group(2)) * factor
        elif kind == "collective-permute":
            traffic = op_bytes
        else:  # reduce-scatter, all-to-all
            traffic = op_bytes * factor
        for ax in varying:
            out[ax] += traffic / len(varying)
    return out
