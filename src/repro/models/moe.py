"""Mixture-of-Experts block with exact-FLOPs scatter/gather dispatch.

Design (TPU-adapted, see DESIGN.md):
  * experts are sharded over the ``model`` mesh axis (expert parallelism);
    the batch stays sharded over (pod, data) — dispatch/combine never cross
    batch shards, so there is no all-to-all; expert weights are FSDP-sharded
    over ``data`` at rest and all-gathered per layer like dense weights.
  * dispatch uses capacity-based scatter-add (k python-unrolled scatters of
    (B,S,d)), expert compute is a batched einsum over (E, C, d) — HLO FLOPs
    equal useful FLOPs (tokens × top_k × cf), unlike the classic one-hot
    einsum dispatch which inflates FLOPs by O(E·C/d_ff).
  * ``moe_impl='einsum'`` is the small-shape oracle used in tests.

Capacity is per sequence group (G = seq_len tokens): C = ceil(G·k·cf/E),
rounded up to a multiple of 8. Overflowing assignments are dropped (standard
capacity-factor semantics); the router load-balance aux loss keeps overflow
rare in real training.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.sharding import ShardingRules, constrain


def capacity(cfg, seq_len: int) -> int:
    c = int(np.ceil(seq_len * cfg.top_k * cfg.capacity_factor / cfg.n_experts))
    return max(8, ((c + 7) // 8) * 8)


def moe_params(pb, cfg, name: str = "moe"):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    sub = pb.sub(name)
    sub.param("router", (d, E), ("embed", None), scale=0.1)
    if cfg.mlp == "swiglu":
        sub.param("wg", (E, d, ff), ("experts", "embed", None))
        sub.param("wu", (E, d, ff), ("experts", "embed", None))
        sub.param("wd", (E, ff, d), ("experts", None, "embed"))
    else:
        sub.param("w1", (E, d, ff), ("experts", "embed", None))
        sub.param("w2", (E, ff, d), ("experts", None, "embed"))


def _route(x, p, cfg):
    """Router: returns (weights (B,S,k), expert ids (B,S,k), aux load loss)."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, cfg.top_k)
    topv = topv / (jnp.sum(topv, -1, keepdims=True) + 1e-9)
    # switch-style load-balance loss
    E = cfg.n_experts
    me = jnp.mean(probs, axis=(0, 1))                      # mean router prob
    ce = jnp.mean(
        (jax.nn.one_hot(topi[..., 0], E, dtype=jnp.float32)), axis=(0, 1)
    )                                                      # fraction routed (top-1 proxy)
    aux = E * jnp.sum(me * ce)
    return topv, topi, aux


def _expert_ffn(xd, p, cfg):
    """xd: (B, E, C, d) -> (B, E, C, d); batched per-expert MLP."""
    if cfg.mlp == "swiglu":
        g = jnp.einsum("becd,edf->becf", xd, p["wg"])
        u = jnp.einsum("becd,edf->becf", xd, p["wu"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xd.dtype) * u
        return jnp.einsum("becf,efd->becd", h, p["wd"])
    h = jnp.einsum("becd,edf->becf", xd, p["w1"])
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(xd.dtype)
    return jnp.einsum("becf,efd->becd", h, p["w2"])


def moe_block_scatter(x, p, cfg, rules: ShardingRules):
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = capacity(cfg, S)
    topv, topi, aux = _route(x, p, cfg)

    # position-in-expert for every assignment, in (s, k) scan order
    flat_e = topi.reshape(B, S * k)                                   # (B, S*k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)               # (B, S*k, E)
    pos_all = jnp.cumsum(onehot, axis=1) - onehot                     # exclusive count
    pos = jnp.take_along_axis(pos_all, flat_e[..., None], axis=2)[..., 0]  # (B, S*k)
    pos = pos.reshape(B, S, k)
    keep = (pos < C)
    dst = topi * C + jnp.minimum(pos, C - 1)                          # (B, S, k)

    # scatter-add tokens into expert slots; k unrolled scatters of (B,S,d)
    xd_flat = jnp.zeros((B, E * C, d), x.dtype)
    for kk in range(k):
        upd = x * keep[..., kk:kk + 1].astype(x.dtype)
        idx = dst[..., kk]
        xd_flat = jax.vmap(lambda buf, i, u: buf.at[i].add(u))(xd_flat, idx, upd)
    xd = xd_flat.reshape(B, E, C, d)
    xd = constrain(xd, rules, ("batch", "experts", None, None))

    yd = _expert_ffn(xd, p, cfg)
    yd = constrain(yd, rules, ("batch", "experts", None, None))
    yd_flat = yd.reshape(B, E * C, d)
    # E-major reshape keeps dim 1 expert-sharded: the combine gather then
    # partitions as local-gather + mask + psum('model') instead of GSPMD's
    # "involuntary full rematerialization" (a ~2 GB/device f32 all-gather
    # per layer on arctic-480b)
    yd_flat = constrain(yd_flat, rules, ("batch", "experts", None))

    # combine: gather each assignment's output back, weighted.
    # 'manual' does the expert-dim selection inside a shard_map manual over
    # the expert ('model') axis: local gather of locally-owned slots +
    # masked accumulate + one psum — the schedule GSPMD cannot find (its
    # gather partitioner takes the replicate-everything path, Shardy bug
    # b/433785288). 'gather_dshard' kept as the refuted alternative.
    mode = getattr(cfg, "moe_combine", "gather")
    wts = (topv * keep.astype(jnp.float32)).astype(x.dtype)          # (B,S,k)
    if mode == "manual" and rules.axis_for("experts") is not None:
        out = _combine_manual(yd_flat, dst, wts, E * C, rules)
        if out is not None:
            return constrain(out, rules, ("batch", "seq", "embed")), aux
    dshard = mode == "gather_dshard"
    out = jnp.zeros_like(x)
    if dshard:
        out = constrain(out, rules, (None, "seq", "mlp"))
    for kk in range(k):
        g = jnp.take_along_axis(yd_flat, dst[..., kk][..., None], axis=1)  # (B,S,d)
        if dshard:
            g = constrain(g, rules, (None, "seq", "mlp"))
        out = out + g * wts[..., kk][..., None]
    return constrain(out, rules, ("batch", "seq", "embed")), aux


def _combine_manual(yd_flat, dst, wts, EC: int, rules: ShardingRules):
    """Expert-combine with the expert axis manual (see moe_block_scatter)."""
    import jax
    from jax.sharding import PartitionSpec as P

    axis = rules.axis_for("experts")
    mesh = jax.sharding.get_abstract_mesh()  # launch.*'s jax.set_mesh
    if mesh.empty or axis not in mesh.axis_names:
        return None  # caller falls back to the gather path

    def body(yd_local, dst_l, w_l):
        ec_loc = yd_local.shape[1]
        lo = jax.lax.axis_index(axis) * ec_loc
        local = dst_l - lo                                   # (B,S,k)
        valid = (local >= 0) & (local < ec_loc)
        local = jnp.clip(local, 0, ec_loc - 1)
        out = jnp.zeros(yd_local.shape[:1] + dst_l.shape[1:2] + yd_local.shape[-1:],
                        yd_local.dtype)
        for kk in range(dst_l.shape[-1]):
            g = jnp.take_along_axis(yd_local, local[..., kk][..., None], axis=1)
            out = out + g * (w_l[..., kk] * valid[..., kk].astype(w_l.dtype))[..., None]
        return jax.lax.psum(out, axis)

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, axis, None), P(), P()),
        out_specs=P(),
        axis_names={axis},
        check_vma=False,
    )(yd_flat, dst, wts)


def moe_block_einsum(x, p, cfg, rules: ShardingRules):
    """One-hot einsum dispatch (oracle; small shapes only — FLOPs-inflated)."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    C = capacity(cfg, S)
    topv, topi, aux = _route(x, p, cfg)

    flat_e = topi.reshape(B, S * k)
    onehot_e = jax.nn.one_hot(flat_e, E, dtype=jnp.float32)
    pos_all = jnp.cumsum(onehot_e, axis=1) - onehot_e
    pos = jnp.take_along_axis(pos_all, flat_e[..., None], axis=2)[..., 0].reshape(B, S, k)
    keep = (pos < C).astype(jnp.float32)
    pos = jnp.minimum(pos, C - 1)
    # dispatch tensor (B, S, k, E, C)
    de = jax.nn.one_hot(topi, E, dtype=jnp.float32) * keep[..., None]
    dc = jax.nn.one_hot(pos, C, dtype=jnp.float32)
    disp = jnp.einsum("bske,bskc->bsec", de, dc)
    xd = jnp.einsum("bsec,bsd->becd", disp, x.astype(jnp.float32)).astype(x.dtype)
    yd = _expert_ffn(xd, p, cfg)
    comb = jnp.einsum("bske,bskc,bsk->bsec", de, dc, topv)
    out = jnp.einsum("bsec,becd->bsd", comb, yd.astype(jnp.float32)).astype(x.dtype)
    return out, aux


def moe_block(x, p, cfg, rules: ShardingRules):
    impl = moe_block_einsum if cfg.moe_impl == "einsum" else moe_block_scatter
    S = x.shape[1]
    nc = max(1, min(cfg.moe_seq_chunks, S))
    while S % nc:
        nc -= 1
    if nc == 1:
        return impl(x, p, cfg, rules)
    outs, aux = [], 0.0
    for i in range(nc):
        sl = slice(i * (S // nc), (i + 1) * (S // nc))
        o, a = impl(x[:, sl], p, cfg, rules)
        outs.append(o)
        aux = aux + a
    return jnp.concatenate(outs, axis=1), aux / nc
