"""Roofline-term extraction from compiled dry-run artifacts.

Per-cell terms (seconds, per the system prompt):
    compute    = HLO_FLOPs / PEAK_FLOPS          (per device — cost_analysis
                                                  is already post-SPMD)
    memory     = HLO_bytes / HBM_BW
    collective = Σ_axis axis_bytes / link_BW     (ICI for data/model axes,
                                                  DCI for the pod axis)

XLA counts while/scan bodies ONCE (verified empirically in this repo), so
whole-program cost_analysis under scan-over-layers undercounts by ~n_layers.
We therefore lower *segments* — one repeated unit (with exact-causal
unrolled attention, ``attn_accounting=True``), the embed+head remainder,
the tail — and combine analytically:

    cost(cell) = n_units·C(unit) + [C(1-unit model) − C(unit)] + C(tail)

Collective bytes come from parsing the compiled HLO text: every
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute
op's operand bytes × the ring-algorithm factor, attributed to the mesh axis
its replica group spans (device-id → mesh-coordinate mapping).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

import numpy as np

from repro.launch import hw

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


def _parse_shape_bytes(shape_str: str) -> int:
    """bytes of 'f32[16,128]{...}' or tuple '(f32[...], u32[...])'."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _symbol_shapes(txt: str) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for m in re.finditer(r"%([\w.-]+) = (\([^)]*\)|\w+\[[\d,]*\]\S*)", txt):
        out.setdefault(m.group(1), m.group(2))
    return out


def _replica_groups(line: str) -> Optional[List[List[int]]]:
    m = re.search(r"replica_groups=\{\{([^}]*(?:\},\{[^}]*)*)\}\}", line)
    if m:
        return [[int(x) for x in grp.split(",") if x.strip() != ""]
                for grp in m.group(1).split("},{")]
    # iota form: replica_groups=[8,64]<=[16,2,16]T(1,0,2) or <=[512]
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?",
                  line)
    if m:
        ng, gs = int(m.group(1)), int(m.group(2))
        reshape = [int(x) for x in m.group(3).split(",")]
        ids = np.arange(int(np.prod(reshape)))
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            ids = ids.reshape(reshape).transpose(perm).reshape(-1)
        return ids.reshape(ng, gs).tolist()
    return None


@dataclasses.dataclass
class CollectiveStats:
    per_axis_bytes: Dict[str, float]      # per-device traffic by mesh axis
    n_ops: int

    def total(self) -> float:
        return sum(self.per_axis_bytes.values())


def parse_collectives(txt: str, mesh) -> CollectiveStats:
    """Per-device collective bytes by mesh axis from HLO text.

    Preferred input is the post-SPMD-partitioning pass dump: collective
    dtypes there are the TPU-target ones (the CPU backend later promotes
    bf16 GEMM regions to f32, dragging converts across collectives and
    doubling their apparent bytes — a host-compile artifact). At that stage
    the partitioner emits all-reduce + dynamic-slice where later passes
    form reduce-scatter, so ARs whose value is only consumed by
    dynamic-slice are costed as reduce-scatters.
    """
    from repro.launch.mesh import device_coords
    coords = device_coords(mesh)
    axis_names = tuple(mesh.axis_names)
    shapes = _symbol_shapes(txt)
    per_axis = {a: 0.0 for a in axis_names}
    per_axis["unknown"] = 0.0
    n_ops = 0

    for line in txt.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if "-done" in line.split("=")[1][:40]:
            continue
        out_shape, kind, operands = m.group(2), m.group(3), m.group(4)
        name = m.group(1)
        groups = _replica_groups(line)
        if groups is None or len(groups[0]) <= 1:
            continue
        g = len(groups[0])
        if kind == "all-reduce":
            # AR whose only consumers are dynamic-slices == reduce-scatter
            esc = re.escape(name)
            use_lines = [l for l in txt.splitlines()
                         if re.search(r"[(,] ?%" + esc + r"\b", l)
                         and not re.match(r"\s*%" + esc + r"\s*=", l)]
            if use_lines and all(" dynamic-slice(" in l or "_dynamic-slice_" in l
                                 for l in use_lines):
                kind = "reduce-scatter"
        # which axes vary inside one group?
        varying = set()
        base = coords.get(groups[0][0])
        for dev in groups[0][1:]:
            c = coords.get(dev)
            if base is None or c is None:
                varying.add("unknown")
                break
            for ax, (a, b) in zip(axis_names, zip(base, c)):
                if a != b:
                    varying.add(ax)
        # operand bytes (first operand's shape; all-reduce may be variadic)
        op_bytes = 0
        for op in operands.split(","):
            op = op.strip()
            name = op.lstrip("%").split(" ")[0]
            if name in shapes:
                op_bytes += _parse_shape_bytes(shapes[name])
            else:
                sm = _SHAPE_RE.search(op)
                if sm:
                    op_bytes += _parse_shape_bytes(op)
        out_bytes = _parse_shape_bytes(out_shape)
        factor = (g - 1) / g
        if kind == "all-reduce":
            traffic = 2.0 * op_bytes * factor
        elif kind == "all-gather":
            traffic = out_bytes * factor
        elif kind == "reduce-scatter":
            traffic = op_bytes * factor
        elif kind == "all-to-all":
            traffic = op_bytes * factor
        else:  # collective-permute
            traffic = op_bytes
        n_ops += 1
        share = traffic / max(1, len(varying))
        for ax in (varying or {"unknown"}):
            per_axis[ax] = per_axis.get(ax, 0.0) + share
    return CollectiveStats(per_axis_bytes=per_axis, n_ops=n_ops)


@dataclasses.dataclass
class SegmentCost:
    flops: float            # per device
    bytes_hbm: float        # per device ('bytes accessed')
    coll: Dict[str, float]  # per device, by axis
    peak_mem: float         # temp bytes per device (memory_analysis)

    def __add__(self, o):
        coll = dict(self.coll)
        for k, v in o.coll.items():
            coll[k] = coll.get(k, 0.0) + v
        return SegmentCost(self.flops + o.flops, self.bytes_hbm + o.bytes_hbm,
                           coll, max(self.peak_mem, o.peak_mem))

    def scaled(self, n: float):
        return SegmentCost(self.flops * n, self.bytes_hbm * n,
                           {k: v * n for k, v in self.coll.items()}, self.peak_mem)

    def minus(self, o):
        coll = {k: max(0.0, v - o.coll.get(k, 0.0)) for k, v in self.coll.items()}
        return SegmentCost(max(0.0, self.flops - o.flops),
                           max(0.0, self.bytes_hbm - o.bytes_hbm),
                           coll, self.peak_mem)


def cost_of_compiled(compiled, mesh, txt_override: Optional[str] = None) -> SegmentCost:
    ca = compiled.cost_analysis()
    txt = txt_override if txt_override is not None else compiled.as_text()
    coll = parse_collectives(txt, mesh)
    ma = compiled.memory_analysis()
    return SegmentCost(
        flops=float(ca.get("flops", 0.0)),
        bytes_hbm=float(ca.get("bytes accessed", 0.0)),
        coll=coll.per_axis_bytes,
        peak_mem=float(ma.temp_size_in_bytes),
    )


def compile_with_spmd_dump(lowered, mesh) -> SegmentCost:
    """Compile + cost, reading collectives from the post-SPMD pass dump when
    available (REPRO_XLA_DUMP set by the dry-run launcher) — see
    parse_collectives for why the final executable text misleads on CPU."""
    import os
    dump_dir = os.environ.get("REPRO_XLA_DUMP", "")
    before = set(os.listdir(dump_dir)) if os.path.isdir(dump_dir) else set()
    compiled = lowered.compile()
    txt = None
    if dump_dir and os.path.isdir(dump_dir):
        new = [f for f in os.listdir(dump_dir)
               if f not in before and "after_spmd-partitioning" in f]
        if new:
            p = max((os.path.join(dump_dir, f) for f in new),
                    key=os.path.getmtime)
            with open(p) as fh:
                txt = fh.read()
    return cost_of_compiled(compiled, mesh, txt_override=txt)


def roofline_terms(cost: SegmentCost, mesh) -> Dict[str, float]:
    """The three terms in seconds (+ diagnostics)."""
    compute_s = cost.flops / hw.PEAK_FLOPS_BF16
    memory_s = cost.bytes_hbm / hw.HBM_BW
    coll_s = 0.0
    for ax, b in cost.coll.items():
        if ax == "pod":
            coll_s += b / hw.DCI_BW
        elif ax == "unknown":
            coll_s += b / hw.ICI_BW
        else:
            coll_s += b / hw.ICI_BW
    dominant = max(
        [("compute", compute_s), ("memory", memory_s), ("collective", coll_s)],
        key=lambda kv: kv[1])[0]
    bound = max(compute_s, memory_s, coll_s)
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": coll_s,
        "dominant": dominant,
        "roofline_frac": compute_s / bound if bound > 0 else 0.0,
        "coll_pod_bytes": cost.coll.get("pod", 0.0),
        "coll_ici_bytes": sum(v for k, v in cost.coll.items() if k != "pod"),
        "peak_mem_gb": cost.peak_mem / 1e9,
    }
