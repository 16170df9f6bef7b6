"""Unified decoder assembly for every assigned LM-family architecture.

A model is a repeating unit of sublayers (``cfg.block_pattern``) scanned
``cfg.n_units`` times plus an explicit (short) tail. Sublayer kinds:

  * ``attn``  — self-attention (GQA/MQA, optional sliding window, optional
                QKV bias) + MLP or MoE (optionally with arctic's parallel
                dense residual MLP)
  * ``cross`` — cross-attention to stub media embeddings (VLM) + MLP
  * ``rglru`` — Griffin recurrent block + MLP
  * ``rwkv``  — RWKV6 time-mix + channel-mix

Entry points: ``init_params``, ``loss_fn`` (train), ``prefill``,
``decode_step`` (serve). All are pure functions over (params, batch);
sharding is injected via ShardingRules + with_sharding_constraint only, so
the same code lowers on any mesh.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.sharding import ShardingRules, constrain
from repro.models import layers as L
from repro.models import moe as M
from repro.models import rglru as R
from repro.models import rwkv6 as W
from repro.models.config import ModelConfig
from repro.models.param import ParamBuilder


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _sublayer_params(pb: ParamBuilder, cfg: ModelConfig, kind: str, tp: int):
    if kind in ("attn", "cross"):
        L.norm_params(pb, "norm1", cfg.d_model, cfg.norm)
        L.attn_params(pb, cfg, tp)
        L.norm_params(pb, "norm2", cfg.d_model, cfg.norm)
        if cfg.n_experts:
            M.moe_params(pb, cfg)
            if cfg.dense_residual:
                L.mlp_params(pb, cfg)
        else:
            L.mlp_params(pb, cfg)
    elif kind == "rglru":
        L.norm_params(pb, "norm1", cfg.d_model, cfg.norm)
        R.rglru_params(pb, cfg)
        L.norm_params(pb, "norm2", cfg.d_model, cfg.norm)
        L.mlp_params(pb, cfg)
    elif kind == "rwkv":
        L.norm_params(pb, "norm1", cfg.d_model, cfg.norm)
        W.rwkv_time_params(pb, cfg)
        L.norm_params(pb, "norm2", cfg.d_model, cfg.norm)
        W.rwkv_channel_params(pb, cfg)
    else:
        raise ValueError(kind)


def init_params(cfg: ModelConfig, key=None, abstract: bool = False, tp: int = 16):
    """Returns (params, logical_axes) — both nested dicts of identical shape.

    abstract=True builds ShapeDtypeStructs (no allocation).
    """
    dtype = jnp.dtype(cfg.dtype)
    if key is None and not abstract:
        key = jax.random.PRNGKey(0)
    pb = ParamBuilder(key, dtype, abstract)

    V, d = cfg.vocab_size, cfg.d_model
    pb.param("embed", (V, d), ("vocab_rows", "tensor_cols"), scale=1.0)
    if cfg.frontend == "frames":
        pb.param("frame_proj", (d, d), ("embed", "mlp"))
    if cfg.frontend == "patches":
        pb.param("patch_proj", (d, d), ("embed", "mlp"))

    # one scanned "unit" = one repetition of block_pattern, stacked n_units x
    unit = pb.sub("unit")
    for i, kind in enumerate(cfg.block_pattern):
        _sublayer_params(unit.sub(f"{i}_{kind}"), cfg, kind, tp)
    # tail layers (pattern remainder), unstacked
    tail = pb.sub("tail")
    for i, kind in enumerate(cfg.tail_pattern):
        _sublayer_params(tail.sub(f"{i}_{kind}"), cfg, kind, tp)

    L.norm_params(pb, "final_norm", d, cfg.norm)
    if not cfg.tie_embeddings:
        pb.param("lm_head", (d, V), ("embed", "vocab"))
    params, logical = pb.build()

    # stack the unit params over layers
    n = cfg.n_units
    if abstract:
        params["unit"] = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((n,) + tuple(x.shape), x.dtype), params["unit"])
    else:
        # re-init stacked: draw (n, ...) in one shot for distinct per-layer
        # values, from the caller's key (the same key gives the same weights
        # in every process)
        key2 = jax.random.fold_in(key, n)
        flat, treedef = jax.tree.flatten(params["unit"])
        new = []
        for i, x in enumerate(flat):
            key2, sub = jax.random.split(key2)
            if jnp.issubdtype(x.dtype, jnp.floating) and x.ndim >= 2:
                std = 1.0 / np.sqrt(max(1, x.shape[0]))
                new.append((jax.random.normal(sub, (n,) + x.shape, jnp.float32) * std
                            ).astype(x.dtype))
            else:
                new.append(jnp.broadcast_to(x, (n,) + x.shape))
        params["unit"] = jax.tree.unflatten(treedef, new)
    logical["unit"] = jax.tree.map(
        lambda lg: ("layers",) + lg, logical["unit"],
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x))
    return params, logical


# ---------------------------------------------------------------------------
# sublayer application
# ---------------------------------------------------------------------------

def _apply_sublayer(x, p, cfg: ModelConfig, rules, kind: str, positions,
                    cache=None, media=None):
    """Returns (x, new_cache). cache=None in training."""
    aux = 0.0
    if kind in ("attn", "cross"):
        h = L.norm(x, p["norm1"], cfg.norm, cfg.norm_eps)
        if kind == "attn":
            window = cfg.window
            a, new_cache = L.self_attention(
                h, p["attn"], cfg, rules, positions, window=window, cache=cache)
        else:
            a = L.cross_attention(h, p["attn"], cfg, rules, media)
            new_cache = cache if cache is not None else None
        x = x + a
        h = L.norm(x, p["norm2"], cfg.norm, cfg.norm_eps)
        if cfg.n_experts:
            mo, aux = M.moe_block(h, p["moe"], cfg, rules)
            if cfg.dense_residual:
                mo = mo + L.mlp_block(h, p["mlp"], cfg, rules)
        else:
            mo = L.mlp_block(h, p["mlp"], cfg, rules)
        x = x + mo
    elif kind == "rglru":
        h = L.norm(x, p["norm1"], cfg.norm, cfg.norm_eps)
        a, new_cache = R.rglru_block(h, p["rglru"], cfg, rules, state=cache)
        x = x + a
        h = L.norm(x, p["norm2"], cfg.norm, cfg.norm_eps)
        x = x + L.mlp_block(h, p["mlp"], cfg, rules)
    elif kind == "rwkv":
        h = L.norm(x, p["norm1"], cfg.norm, cfg.norm_eps)
        a, tstate = W.rwkv_time_mix(h, p["time"], cfg, rules,
                                    state=None if cache is None else cache["time"])
        x = x + a
        h = L.norm(x, p["norm2"], cfg.norm, cfg.norm_eps)
        c, cstate = W.rwkv_channel_mix(h, p["channel"], cfg, rules,
                                       state=None if cache is None else cache["channel"])
        x = x + c
        new_cache = {"time": tstate, "channel": cstate}
    else:
        raise ValueError(kind)
    return x, new_cache, aux


def _apply_unit(x, unit_p, cfg, rules, positions, unit_cache=None, media=None):
    new_cache = {}
    aux_total = 0.0
    for i, kind in enumerate(cfg.block_pattern):
        key = f"{i}_{kind}"
        c = None if unit_cache is None else unit_cache.get(key)
        x, nc, aux = _apply_sublayer(x, unit_p[key], cfg, rules, kind, positions,
                                     cache=c, media=media)
        new_cache[key] = nc
        aux_total = aux_total + aux
    return x, new_cache, aux_total


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def embed_inputs(params, batch: Dict[str, Any], cfg: ModelConfig, rules):
    """Returns (x (B,S,d), media (B,T,d) or None, labels (B,S), positions)."""
    dtype = jnp.dtype(cfg.dtype)
    media = None
    if cfg.frontend == "frames":
        # musicgen: precomputed EnCodec frame embeddings (stub frontend)
        x = jnp.einsum("bsd,de->bse", batch["frames"].astype(dtype), params["frame_proj"])
        labels = batch["labels"]
    else:
        tokens = batch["tokens"]
        # gather from a (V→fsdp, d→replicated) view: GSPMD's gather
        # partitioner mishandles a d-sharded table under the microbatch scan
        # (dynamic-slice size > shard bug); the reshard is ~MBs and CSE'd.
        table = constrain(params["embed"], rules, ("vocab_rows", None))
        x = jnp.take(table, tokens, axis=0).astype(dtype)
        if cfg.embed_scale:
            x = x * float(np.sqrt(cfg.d_model))  # python float: keeps bf16
        labels = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
        if cfg.frontend == "patches":
            media = jnp.einsum("btd,de->bte", batch["patches"].astype(dtype),
                               params["patch_proj"])
    B, S = x.shape[0], x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = constrain(x, rules, ("batch", "seq", None))
    return x, media, labels, positions


def unembed(params, x, cfg: ModelConfig, rules):
    x = L.norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        # the table at rest is (V→fsdp, d→tensor); for the logits matmul we
        # need V on the tensor axis (else GSPMD replicates the (B,S,V)
        # logits — a ~3.3 GB/device all-gather per loss chunk). One cheap
        # table reshard per step instead, CSE'd across loss chunks.
        table = constrain(params["embed"], rules, ("vocab", None))
        logits = jnp.einsum("bsd,vd->bsv", x, table)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return constrain(logits, rules, ("batch", "seq", "vocab"))


def _xent(logits, labels, mask):
    """Token-mean cross entropy, fp32, vocab-sharding-native.

    No gather on the vocab axis: the gold logit is a one-hot-masked sum
    (local partial + tiny (B,S) psum under GSPMD) and logsumexp reduces
    locally before the cross-shard max/sum — keeps the (B,S,V) tensor
    sharded over 'model' end to end (a replicated-logits all-gather here
    costs ~3.3 GB/device/chunk at vocab 50k; see EXPERIMENTS.md §Perf).
    """
    logits = logits.astype(jnp.float32)
    V = logits.shape[-1]
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[..., 0]
    onehot = (jnp.arange(V, dtype=jnp.int32)[None, None, :]
              == labels[..., None].astype(jnp.int32))
    gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    nll = (lse - gold) * mask
    return jnp.sum(nll), jnp.sum(mask)


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------

def _unit_step_fn(cfg, rules, media):
    def step(x, unit_p, positions):
        y, _, aux = _apply_unit(x, unit_p, cfg, rules, positions, media=media)
        return y, aux
    if cfg.remat == "full":
        step = jax.checkpoint(step, policy=jax.checkpoint_policies.nothing_saveable)
    elif cfg.remat == "dots":
        step = jax.checkpoint(
            step, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return step


def forward(params, batch, cfg: ModelConfig, rules: ShardingRules):
    """Full training-style forward: returns (pre-head activations, labels, aux)."""
    x, media, labels, positions = embed_inputs(params, batch, cfg, rules)
    step = _unit_step_fn(cfg, rules, media)

    aux_total = 0.0
    if cfg.scan_layers and cfg.n_units > 1:
        def body(carry, unit_p):
            y, aux = step(carry, unit_p, positions)
            return y, aux
        x, auxs = jax.lax.scan(body, x, params["unit"])
        aux_total = aux_total + jnp.sum(jnp.asarray(auxs))
    else:
        for i in range(cfg.n_units):
            unit_p = jax.tree.map(lambda t: t[i], params["unit"])
            x, aux = step(x, unit_p, positions)
            aux_total = aux_total + aux
    for i, kind in enumerate(cfg.tail_pattern):
        x, _, aux = _apply_sublayer(x, params["tail"][f"{i}_{kind}"], cfg, rules,
                                    kind, positions, media=media)
        aux_total = aux_total + aux
    return x, labels, aux_total


def loss_fn(params, batch, cfg: ModelConfig, rules: ShardingRules):
    """Scalar mean loss (+ metrics dict). Head is applied in sequence chunks
    so the (B, S, vocab) logits tensor never fully materializes."""
    x, labels, aux = forward(params, batch, cfg, rules)
    B, S, _ = x.shape
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
        if cfg.frontend != "frames":
            mask = mask.at[:, -1].set(0.0)  # shifted labels: last position void
    nc = max(1, min(cfg.loss_chunks, S))
    while S % nc:
        nc -= 1
    tot, cnt = 0.0, 0.0
    for i in range(nc):
        sl = slice(i * (S // nc), (i + 1) * (S // nc))
        logits = unembed(params, x[:, sl], cfg, rules)
        t, c = _xent(logits, labels[:, sl], mask[:, sl])
        tot, cnt = tot + t, cnt + c
    loss = tot / jnp.maximum(cnt, 1.0)
    if cfg.n_experts:
        loss = loss + 0.01 * aux / max(1, cfg.n_layers)
    return loss, {"xent": tot / jnp.maximum(cnt, 1.0), "aux": aux}


# ---------------------------------------------------------------------------
# serve: prefill + decode
# ---------------------------------------------------------------------------

def _cache_struct(cfg: ModelConfig, kind: str, batch: int, cache_len: int,
                  dtype, abstract: bool):
    mk = (lambda s, dt: jax.ShapeDtypeStruct(s, dt)) if abstract else \
         (lambda s, dt: jnp.zeros(s, dt))
    if kind == "attn":
        clen = min(cache_len, cfg.window) if cfg.window else cache_len
        kv = (batch, clen, cfg.n_kv_heads, cfg.head_dim)
        return {"k": mk(kv, dtype), "v": mk(kv, dtype),
                "pos": mk((), jnp.int32)}
    if kind == "cross":
        # media embeddings are passed per step via batch["media"] (stub
        # frontend) — no per-layer cache, avoiding n_units duplication
        return {"pos": mk((), jnp.int32)}
    if kind == "rglru":
        return (R.rglru_state_abstract(cfg, batch, dtype) if abstract
                else R.rglru_init_state(cfg, batch, dtype))
    if kind == "rwkv":
        return (W.rwkv_state_abstract(cfg, batch, dtype) if abstract
                else W.rwkv_init_state(cfg, batch, dtype))
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, cache_len: int, abstract: bool = False):
    """Cache pytree: per-unit-sublayer stacked over n_units + tail list."""
    dtype = jnp.dtype(cfg.dtype)
    unit = {}
    for i, kind in enumerate(cfg.block_pattern):
        c = _cache_struct(cfg, kind, batch, cache_len, dtype, abstract)
        n = cfg.n_units
        unit[f"{i}_{kind}"] = jax.tree.map(
            lambda x: (jax.ShapeDtypeStruct((n,) + tuple(x.shape), x.dtype)
                       if abstract else jnp.broadcast_to(x, (n,) + x.shape).copy()), c)
    tail = {}
    for i, kind in enumerate(cfg.tail_pattern):
        tail[f"{i}_{kind}"] = _cache_struct(cfg, kind, batch, cache_len, dtype, abstract)
    return {"unit": unit, "tail": tail}


def decode_step(params, batch, cache, cfg: ModelConfig, rules: ShardingRules):
    """One-token decode: batch = {'tokens': (B,1)} (or {'frames': (B,1,d)}).

    Returns (logits (B, vocab), new_cache). Media cross-attn KV comes from
    cache['media'] written at prefill (stub frontends: provided directly).
    """
    dtype = jnp.dtype(cfg.dtype)
    if cfg.frontend == "frames":
        x = jnp.einsum("bsd,de->bse", batch["frames"].astype(dtype), params["frame_proj"])
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(dtype)
        if cfg.embed_scale:
            x = x * float(np.sqrt(cfg.d_model))  # python float: keeps bf16
    pos = batch["pos"]                                    # (B, 1) int32 absolute
    media = batch.get("media")
    if media is not None:
        media = jnp.einsum("btd,de->bte", media.astype(dtype), params["patch_proj"])

    x = constrain(x, rules, ("batch", None, None))

    def unit_body(x, scanned):
        unit_p, unit_c = scanned
        y, nc, _ = _apply_unit(x, unit_p, cfg, rules, pos, unit_cache=unit_c, media=media)
        return y, nc

    if cfg.scan_layers and cfg.n_units > 1:
        x, new_unit_cache = jax.lax.scan(unit_body, x, (params["unit"], cache["unit"]))
    else:
        ncs = []
        for i in range(cfg.n_units):
            up = jax.tree.map(lambda t: t[i], params["unit"])
            uc = jax.tree.map(lambda t: t[i], cache["unit"])
            x, nc = unit_body(x, (up, uc))
            ncs.append(nc)
        new_unit_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *ncs) if ncs else cache["unit"]

    new_tail = {}
    for i, kind in enumerate(cfg.tail_pattern):
        key = f"{i}_{kind}"
        x, nc, _ = _apply_sublayer(x, params["tail"][key], cfg, rules, kind, pos,
                                   cache=cache["tail"][key], media=media)
        new_tail[key] = nc
    logits = unembed(params, x, cfg, rules)[:, -1]
    return logits, {"unit": new_unit_cache, "tail": new_tail}


def prefill(params, batch, cfg: ModelConfig, rules: ShardingRules, cache_len: int):
    """Process a full prompt, returning (last-position logits, filled cache).

    Implemented as forward + cache write (train-style chunked attention);
    recurrent layers hand back their final states directly.
    """
    x, media, labels, positions = embed_inputs(params, batch, cfg, rules)
    B, S = positions.shape
    cache = init_cache(cfg, B, cache_len)

    def unit_body(x, scanned):
        unit_p, unit_c = scanned
        new_c = {}
        y = x
        for i, kind in enumerate(cfg.block_pattern):
            key = f"{i}_{kind}"
            y, nc, _ = _apply_sublayer(y, unit_p[key], cfg, rules, kind, positions,
                                       cache=None, media=media)
            if kind == "attn":
                # write the K/V computed during the causal pass into the cache
                k, v = nc
                clen = unit_c[key]["k"].shape[1]
                if clen < S:
                    k, v = k[:, -clen:], v[:, -clen:]
                    nc_new = {"k": k, "v": v, "pos": jnp.asarray(S, jnp.int32)}
                else:
                    kbuf = jax.lax.dynamic_update_slice(unit_c[key]["k"], k, (0, 0, 0, 0))
                    vbuf = jax.lax.dynamic_update_slice(unit_c[key]["v"], v, (0, 0, 0, 0))
                    nc_new = {"k": kbuf, "v": vbuf, "pos": jnp.asarray(S, jnp.int32)}
                new_c[key] = nc_new
            elif kind == "cross":
                new_c[key] = {"pos": jnp.asarray(S, jnp.int32)}
            else:
                new_c[key] = nc
        return y, new_c

    if cfg.scan_layers and cfg.n_units > 1:
        x, new_unit_cache = jax.lax.scan(unit_body, x, (params["unit"], cache["unit"]))
    else:
        ncs = []
        for i in range(cfg.n_units):
            up = jax.tree.map(lambda t: t[i], params["unit"])
            uc = jax.tree.map(lambda t: t[i], cache["unit"])
            x, nc = unit_body(x, (up, uc))
            ncs.append(nc)
        new_unit_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *ncs) if ncs else cache["unit"]

    new_tail = {}
    for i, kind in enumerate(cfg.tail_pattern):
        key = f"{i}_{kind}"
        x, nc, _ = _apply_sublayer(x, params["tail"][key], cfg, rules, kind, positions,
                                   cache=None, media=media)
        if kind == "attn":
            k, v = nc
            clen = cache["tail"][key]["k"].shape[1]
            if clen < S:
                nc = {"k": k[:, -clen:], "v": v[:, -clen:], "pos": jnp.asarray(S, jnp.int32)}
            else:
                nc = {"k": jax.lax.dynamic_update_slice(cache["tail"][key]["k"], k, (0, 0, 0, 0)),
                      "v": jax.lax.dynamic_update_slice(cache["tail"][key]["v"], v, (0, 0, 0, 0)),
                      "pos": jnp.asarray(S, jnp.int32)}
        elif kind == "cross":
            nc = {"pos": jnp.asarray(S, jnp.int32)}
        new_tail[key] = nc
    logits = unembed(params, x[:, -1:], cfg, rules)[:, -1]
    return logits, {"unit": new_unit_cache, "tail": new_tail}
