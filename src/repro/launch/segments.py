"""Segment lowering for exact roofline accounting (see roofline.py).

cost(cell) = C(1-unit model step) + (n_units−1)·C(unit) + C(tail unit)

Each segment is lowered with ``scan_layers=False`` and
``attn_accounting=True`` (static-causal unrolled attention → no while
loops, exact-causal FLOPs) on the production mesh with the cell's real
shardings, so cost_analysis/HLO-parse per segment is exact per device.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro.common.sharding import ShardingRules, filter_valid_spec, logical_to_physical
from repro.launch import specs as S
from repro.launch.roofline import SegmentCost, compile_with_spmd_dump
from repro.models import transformer
from repro.models.config import ModelConfig, ShapeConfig


def _acc(cfg: ModelConfig, pattern=None, n_layers=None) -> ModelConfig:
    return dataclasses.replace(
        cfg,
        block_pattern=tuple(pattern or cfg.block_pattern),
        n_layers=int(n_layers if n_layers is not None else len(pattern or cfg.block_pattern)),
        scan_layers=False,
        attn_accounting=True,
    )


def _x_struct(cfg: ModelConfig, shp: ShapeConfig, mesh: Mesh, rules: ShardingRules,
              decode: bool):
    B = shp.global_batch
    Sq = 1 if decode else shp.seq_len
    shape = (B, Sq, cfg.d_model)
    spec = filter_valid_spec(mesh, logical_to_physical(rules, ("batch", None, None)), shape)
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=NamedSharding(mesh, spec))


def _unit_params_struct(cfg1: ModelConfig, mesh: Mesh, rules: ShardingRules):
    """Abstract params of a 1-unit model, restricted to the unit subtree."""
    abs_p, shard = S.param_shardings(cfg1, mesh, rules)
    return abs_p, shard


def lower_unit_segment(cfg: ModelConfig, shp: ShapeConfig, mesh: Mesh,
                       rules: ShardingRules, pattern=None) -> SegmentCost:
    """Grad (train) or apply (serve) of ONE repeated unit."""
    cfg1 = _acc(cfg, pattern)
    abs_p, _ = S.param_shardings(cfg1, mesh, rules)
    unit_p = abs_p["unit"]  # (1, ...) stacked
    decode = shp.kind == "decode"
    x = _x_struct(cfg1, shp, mesh, rules, decode)
    B = shp.global_batch
    Sq = 1 if decode else shp.seq_len
    pos_spec = filter_valid_spec(
        mesh, logical_to_physical(rules, ("batch", None)), (B, Sq))
    positions = jax.ShapeDtypeStruct((B, Sq), jnp.int32,
                                     sharding=NamedSharding(mesh, pos_spec))
    media_arg = ()
    if "cross" in cfg1.block_pattern:
        mshape = (B, cfg.n_frontend_tokens, cfg.d_model)
        mspec = filter_valid_spec(
            mesh, logical_to_physical(rules, ("batch", None, None)), mshape)
        media_arg = (jax.ShapeDtypeStruct(mshape, jnp.bfloat16,
                                          sharding=NamedSharding(mesh, mspec)),)

    if shp.kind == "train":
        def seg(up, x, positions, *media_a):
            med = media_a[0] if media_a else None
            step = transformer._unit_step_fn(cfg1, rules, med, True)
            up0 = jax.tree.map(lambda t: t[0], up)
            y, _ = step(x, up0, positions)
            return jnp.sum(y.astype(jnp.float32))

        fn = jax.grad(seg, argnums=(0, 1))
        lowered = jax.jit(fn).lower(unit_p, x, positions, *media_arg)
    elif shp.kind == "prefill":
        def seg(up, x, positions, *media_a):
            med = media_a[0] if media_a else None
            up0 = jax.tree.map(lambda t: t[0], up)
            y, nc, _ = transformer._apply_unit(
                x, up0, cfg1, rules, positions, media=med, accounting=True)
            return y, nc  # cache K/V come back as ys (written by prefill)

        lowered = jax.jit(seg).lower(unit_p, x, positions, *media_arg)
    else:  # decode: one token against the cell's cache
        cache = S.cache_struct_sharded(cfg1, shp, mesh, rules)["unit"]

        def seg(up, x, positions, cache, *media_a):
            med = media_a[0] if media_a else None
            up0 = jax.tree.map(lambda t: t[0], up)
            c0 = jax.tree.map(lambda t: t[0], cache)
            y, nc, _ = transformer._apply_unit(
                x, up0, cfg1, rules, positions, unit_cache=c0, media=med)
            return y, nc

        lowered = jax.jit(seg).lower(unit_p, x, positions, cache, *media_arg)
    return compile_with_spmd_dump(lowered, mesh)


def lower_model1_segment(cfg: ModelConfig, shp: ShapeConfig, mesh: Mesh,
                         rules: ShardingRules, opt_name: str,
                         transport: str = "gspmd") -> SegmentCost:
    """Full step of a 1-unit, no-tail model (embed + unit + head [+ opt])."""
    cfg1 = _acc(cfg)
    fn, args, _ = S.input_specs(cfg1, shp, mesh, rules, opt_name,
                                transport=transport)
    lowered = jax.jit(fn).lower(*args)
    return compile_with_spmd_dump(lowered, mesh)


def mixer_fusion_penalty(cfg: ModelConfig, shp: ShapeConfig, mesh: Mesh,
                         rules: ShardingRules) -> Dict[str, float]:
    """Per-layer-kind HBM bytes the Pallas kernels keep in VMEM.

    XLA-CPU's 'bytes accessed' charges every attention-probability /
    rwkv-pair-tensor intermediate to memory; on the TPU target these live in
    VMEM inside kernels/flash_attention.py / rwkv6_scan.py / rglru_scan.py.
    We measure each mixer core standalone at the cell's shapes and subtract
    (measured − ideal-kernel-IO); the roofline reports both raw and fused
    memory terms. Decode mixers (q-len 1) have negligible intermediates.
    """
    if shp.kind == "decode":
        return {}
    train = shp.kind == "train"
    B, Sq = shp.global_batch, shp.seq_len
    out: Dict[str, float] = {}
    from repro.common.sharding import pad_to_multiple

    def spec_of(shape, logical):
        return NamedSharding(mesh, filter_valid_spec(
            mesh, logical_to_physical(rules, logical), shape))

    bspec = lambda s: spec_of(s, ("batch",) + (None,) * (len(s) - 1))
    # mixers must be sharded exactly as embedded: q/r heads on the tensor
    # axis, kv replicated, rnn channels on the tensor axis
    hspec = lambda s: spec_of(s, ("batch", None, "heads", None))
    cspec = lambda s: spec_of(s, ("batch", None, "mlp"))
    kinds = set(cfg.block_pattern) | set(cfg.tail_pattern)

    def measure(fn, args, ideal_io_bytes):
        if train:
            nf = len(args)
            f = lambda *a: jax.grad(
                lambda *aa: jnp.sum(fn(*aa).astype(jnp.float32)),
                argnums=tuple(range(nf)))(*a)
            ideal = 3.0 * ideal_io_bytes          # fwd + recompute-bwd io
        else:
            f = fn
            ideal = ideal_io_bytes
        cost = compile_with_spmd_dump(jax.jit(f).lower(*args), mesh)
        return max(0.0, cost.bytes_hbm - ideal / _ndev(mesh))

    if "attn" in kinds and cfg.n_heads:
        tp = mesh.shape.get("model", 1)
        Hp = pad_to_multiple(cfg.n_heads, tp) if cfg.tp_pad_heads else cfg.n_heads
        hd, KV = cfg.head_dim, cfg.n_kv_heads
        qs = (B, Sq, Hp, hd)
        kvs = (B, Sq, KV, hd)
        q = jax.ShapeDtypeStruct(qs, jnp.bfloat16, sharding=hspec(qs))
        k = jax.ShapeDtypeStruct(kvs, jnp.bfloat16, sharding=bspec(kvs))
        v = jax.ShapeDtypeStruct(kvs, jnp.bfloat16, sharding=bspec(kvs))
        from repro.models.layers import causal_attention
        cfg1 = _acc(cfg)
        fn = lambda q, k, v: causal_attention(q, k, v, cfg1, rules,
                                              window=cfg.window, accounting=True)
        io = 2.0 * (np_prod(qs) * 2 + 2 * np_prod(kvs))  # q,k,v in + o out
        out["attn"] = measure(fn, (q, k, v), io)
    if "rwkv" in kinds:
        from repro.models.rwkv6 import rwkv_heads, _chunk_body
        H, Hp = rwkv_heads(cfg, mesh.shape.get("model", 1))
        hd = cfg.rwkv_head_dim
        shp4 = (B, Sq, Hp, hd)
        mk = lambda dt: jax.ShapeDtypeStruct(shp4, dt, sharding=hspec(shp4))
        r, kk, vv = mk(jnp.bfloat16), mk(jnp.bfloat16), mk(jnp.bfloat16)
        lw = mk(jnp.float32)
        u = jax.ShapeDtypeStruct((Hp, hd), jnp.float32)

        def fn(r, k, v, lw, u):
            W = min(cfg.rwkv_chunk, Sq)
            n = Sq // W
            Sc = jnp.zeros((B, Hp, hd, hd), jnp.float32)
            outs = []
            for i in range(n):
                sl = slice(i * W, (i + 1) * W)
                o, Sc = _chunk_body(r[:, sl], k[:, sl], v[:, sl], lw[:, sl],
                                    u, Sc, None)
                outs.append(o)
            return jnp.concatenate(outs, 1)

        io = 2.0 * 3 * np_prod(shp4) + 4.0 * np_prod(shp4) + 4.0 * np_prod(shp4)
        out["rwkv"] = measure(fn, (r, kk, vv, lw, u), io)
    if "rglru" in kinds:
        from repro.models.rglru import rglru_scan
        shp3 = (B, Sq, cfg.rnn_width)
        a = jax.ShapeDtypeStruct(shp3, jnp.float32, sharding=cspec(shp3))
        b = jax.ShapeDtypeStruct(shp3, jnp.float32, sharding=cspec(shp3))
        io = 3.0 * 4.0 * np_prod(shp3)
        out["rglru"] = measure(lambda a, b: rglru_scan(a, b), (a, b), io)
    return out


def np_prod(shape) -> float:
    out = 1.0
    for s in shape:
        out *= s
    return out


def _ndev(mesh) -> float:
    out = 1.0
    for v in mesh.shape.values():
        out *= v
    return out


def cell_cost(cfg: ModelConfig, shp: ShapeConfig, mesh: Mesh,
              rules: ShardingRules, opt_name: str = "adamw",
              microbatches: int = 1,
              transport: str = "gspmd") -> Dict[str, SegmentCost]:
    """All segments for one cell, combined per the accounting identity.

    With gradient accumulation, segments are lowered at the microbatch size
    and scaled by n_micro — this slightly overcounts the (tiny, elementwise)
    optimizer update which really runs once per step; grad reduce-scatters
    genuinely do run per microbatch (ZeRO semantics), so collectives are
    exact.

    transport='two_step_int8': per-layer gradients are pod-local (GSPMD
    reduces over 'data' only — the ONU step); the one-shot compressed
    cross-pod hop is captured by the model1 segment, which is lowered with
    the real transport train step. Unit segments are therefore lowered with
    per-pod batch and no pod axis in the batch spec.
    """
    if microbatches > 1 and shp.kind == "train":
        shp = dataclasses.replace(
            shp, global_batch=max(1, shp.global_batch // microbatches))
    unit_rules, unit_shp = rules, shp
    if transport == "two_step_int8" and shp.kind == "train" and "pod" in mesh.axis_names:
        n_pod = mesh.shape["pod"]
        unit_rules = rules.with_(batch=("data",))
        unit_shp = dataclasses.replace(
            shp, global_batch=max(1, shp.global_batch // n_pod))
    with jax.set_mesh(mesh):
        c_unit = lower_unit_segment(cfg, unit_shp, mesh, unit_rules)
        c_model1 = lower_model1_segment(cfg, shp, mesh, rules, opt_name,
                                        transport=transport)
        c_tail = None
        if cfg.tail_pattern:
            c_tail = lower_unit_segment(cfg, unit_shp, mesh, unit_rules,
                                        pattern=cfg.tail_pattern)
        penalties = mixer_fusion_penalty(cfg, unit_shp, mesh, unit_rules)
    total = c_model1 + c_unit.scaled(cfg.n_units - 1)
    if c_tail is not None:
        total = total + c_tail
    # kernel-fused memory: subtract VMEM-resident mixer intermediates
    kind_counts: Dict[str, int] = {}
    for k in list(cfg.block_pattern) * cfg.n_units + list(cfg.tail_pattern):
        kind_counts[k] = kind_counts.get(k, 0) + 1
    penalty_total = sum(penalties.get(k, 0.0) * n for k, n in kind_counts.items())
    if microbatches > 1 and shp.kind == "train":
        total = total.scaled(microbatches)
        penalty_total *= microbatches
    fused_bytes = max(total.flops * 0.0, total.bytes_hbm - penalty_total)
    out = {"unit": c_unit, "model1": c_model1, "total": total,
           "fused_bytes": fused_bytes, "mixer_penalties": penalties}
    if c_tail is not None:
        out["tail"] = c_tail
    return out
