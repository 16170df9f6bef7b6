"""Abstract input specs + step functions for every (arch × shape) cell.

``input_specs(cfg, shape, mesh, rules)`` returns ShapeDtypeStructs with
shardings attached (weak-type-correct, shardable, zero allocation) for the
cell's step function:
  train_4k     -> train_step(state, batch)
  prefill_32k  -> prefill_step(params, batch)
  decode_*     -> serve_step(params, batch, cache)   (one new token)

The batch always carries per-client FL metadata: ``client_weight`` (k_ij ·
participation-mask per batch row) — the SFL aggregation weights, folded
into the loss so the gradient *is* the K-normalized weighted aggregate
(DESIGN.md §2).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.sharding import (ShardingRules, filter_valid_spec, logical_to_physical,
                                   param_sharding_tree)
from repro.models import transformer
from repro.models.config import ModelConfig, ShapeConfig
from repro.optim import make_optimizer


def _batch_spec(mesh: Mesh, rules: ShardingRules, shape: Tuple[int, ...]):
    spec = logical_to_physical(rules, ("batch",) + (None,) * (len(shape) - 1))
    return NamedSharding(mesh, filter_valid_spec(mesh, spec, shape))


def batch_struct(cfg: ModelConfig, shp: ShapeConfig, mesh: Mesh,
                 rules: ShardingRules, decode: bool = False) -> Dict[str, Any]:
    B = shp.global_batch
    S = 1 if decode else shp.seq_len
    d = cfg.d_model
    mk = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=_batch_spec(mesh, rules, s))
    batch: Dict[str, Any] = {}
    if cfg.frontend == "frames":
        batch["frames"] = mk((B, S, d), jnp.bfloat16)
        batch["labels"] = mk((B, S), jnp.int32)
    else:
        batch["tokens"] = mk((B, S), jnp.int32)
    if cfg.frontend == "patches":
        key = "media" if decode else "patches"
        batch[key] = mk((B, cfg.n_frontend_tokens, d), jnp.bfloat16)
    if decode:
        batch["pos"] = mk((B, 1), jnp.int32)
    else:
        batch["client_weight"] = mk((B,), jnp.float32)
    return batch


def param_shardings(cfg: ModelConfig, mesh: Mesh, rules: ShardingRules):
    params, logical = transformer.init_params(cfg, abstract=True,
                                              tp=mesh.shape.get("model", 1))
    shard = param_sharding_tree(mesh, rules, params, logical)
    abstract = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        params, shard,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return abstract, shard


def opt_state_struct(opt_name: str, params_abs):
    """Abstract optimizer state (sharded like params, fp32)."""
    make_optimizer(opt_name)    # validates the name before shaping state
    if opt_name in ("sgd",):
        return {}
    f32like = lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32, sharding=x.sharding)
    if opt_name == "sgdm":
        return {"mu": jax.tree.map(f32like, params_abs)}
    if opt_name in ("adamw", "yogi"):
        return {"m": jax.tree.map(f32like, params_abs),
                "v": jax.tree.map(f32like, params_abs),
                "t": jax.ShapeDtypeStruct((), jnp.int32)}
    raise ValueError(opt_name)


def cache_struct_sharded(cfg: ModelConfig, shp: ShapeConfig, mesh: Mesh,
                         rules: ShardingRules):
    cache = transformer.init_cache(cfg, shp.global_batch, shp.seq_len, abstract=True)
    tp = mesh.shape.get("model", 1)

    def shard(x):
        # KV / state buffers: batch over client axes; K/V buffers
        # (layers, B, S, KV, hd) additionally shard over the tensor axis —
        # KV heads when divisible (MHA), else the sequence dim (GQA long
        # caches: 1.07 TB global for deepseek decode_32k — partial-softmax
        # attention over the S-sharded cache is GSPMD-native).
        nd = len(x.shape)
        if nd == 0:
            spec = P()
        elif nd == 5:  # (layers, B, S, KV, hd)
            if cfg.n_kv_heads % tp == 0:
                spec = logical_to_physical(
                    rules, ("layers", "batch", None, "heads", None))
            else:
                spec = logical_to_physical(
                    rules, ("layers", "batch", "heads", None, None))
        else:
            spec = logical_to_physical(rules, ("layers", "batch") + (None,) * (nd - 2)) \
                if nd >= 2 else P(None)
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=NamedSharding(mesh, filter_valid_spec(mesh, spec, x.shape)))

    unit = jax.tree.map(shard, cache["unit"])

    def shard_tail(x):
        nd = len(x.shape)
        spec = logical_to_physical(rules, ("batch",) + (None,) * (nd - 1)) if nd else P()
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=NamedSharding(mesh, filter_valid_spec(mesh, spec, x.shape)))

    tail = jax.tree.map(shard_tail, cache["tail"])
    return {"unit": unit, "tail": tail}


# ---------------------------------------------------------------------------
# step functions
# ---------------------------------------------------------------------------

def weighted_loss_fn(params, batch, cfg: ModelConfig, rules: ShardingRules):
    """FL-weighted loss: per-row client_weight (k_ij · mask), K-normalized.

    With H=1 local step this makes grad(loss) exactly the SFL aggregate
    Σ k·mask·g / K; the reduction schedule (two-step vs flat) is chosen by
    the sharding rules (see DESIGN.md §2)."""
    w = batch.get("client_weight")
    x, labels, aux = transformer.forward(params, batch, cfg, rules)
    B, S, _ = x.shape
    mask = jnp.ones((B, S), jnp.float32)
    if cfg.frontend != "frames":
        mask = mask.at[:, -1].set(0.0)
    if w is not None:
        mask = mask * w[:, None]
    nc = max(1, min(cfg.loss_chunks, S))
    while S % nc:
        nc -= 1
    tot, cnt = 0.0, 0.0
    for i in range(nc):
        sl = slice(i * (S // nc), (i + 1) * (S // nc))
        logits = transformer.unembed(params, x[:, sl], cfg, rules)
        t, c = transformer._xent(logits, labels[:, sl], mask[:, sl])
        tot, cnt = tot + t, cnt + c
    loss = tot / jnp.maximum(cnt, 1e-6)
    if cfg.n_experts:
        loss = loss + 0.01 * aux / max(1, cfg.n_layers)
    return loss, {"xent": loss, "aux": aux}


def unnormalized_loss_fn(params, batch, cfg: ModelConfig, rules: ShardingRules):
    """(Σ weighted nll, Σ weight) — the pre-normalization pieces of the SFL
    objective, for transports that normalize after the cross-pod reduce."""
    w = batch.get("client_weight")
    x, labels, aux = transformer.forward(params, batch, cfg, rules)
    B, S, _ = x.shape
    mask = jnp.ones((B, S), jnp.float32)
    if cfg.frontend != "frames":
        mask = mask.at[:, -1].set(0.0)
    if w is not None:
        mask = mask * w[:, None]
    nc = max(1, min(cfg.loss_chunks, S))
    while S % nc:
        nc -= 1
    tot, cnt = 0.0, 0.0
    for i in range(nc):
        sl = slice(i * (S // nc), (i + 1) * (S // nc))
        logits = transformer.unembed(params, x[:, sl], cfg, rules)
        t, c = transformer._xent(logits, labels[:, sl], mask[:, sl])
        tot, cnt = tot + t, cnt + c
    if cfg.n_experts:
        tot = tot + 0.01 * aux / max(1, cfg.n_layers) * jnp.maximum(cnt, 1.0)
    return tot, cnt


def make_train_step(cfg: ModelConfig, rules: ShardingRules, opt_name: str = "adamw",
                    lr: float = 1e-4, microbatches: int = 1,
                    transport: str = "gspmd", mesh: Optional[Mesh] = None,
                    seed: int = 0):
    """Gradient-accumulated train step.

    microbatches > 1 scans over batch slices, accumulating fp32 grads —
    the standard answer to the L×B×S×d remat-boundary stack (80-layer
    qwen1.5-110b at 16 seqs/device would otherwise save ~86 GB/device).
    Grad reduce-scatter happens per microbatch (ZeRO-style); the optimizer
    and the SFL normalization run once per step.

    transport:
      'gspmd'         — the sharding-induced schedule (reduce-scatter in-pod
                        + all-reduce cross-pod under FSDP rules)
      'two_step_int8' — the paper's protocol made explicit + compressed:
                        shard_map manual over 'pod' (auto data/model), GSPMD
                        reduces within the pod (ONU step), the cross-pod CPS
                        hop all-gathers int8 stochastic-rounded grad shards
                        and dequant-sums; K-normalization after the reduce
                        (exactly Σk·g/K in expectation). Needs a 'pod' axis.
    """
    opt = make_optimizer(opt_name)
    grad_fn = jax.value_and_grad(weighted_loss_fn, has_aux=True)

    if transport == "two_step_int8":
        assert mesh is not None and "pod" in mesh.axis_names
        ugrad = jax.value_and_grad(
            lambda p, b: unnormalized_loss_fn(p, b, cfg, rules), has_aux=True)

        def pod_body(params, opt_state, batch, key):
            # inside: manual over 'pod'; GSPMD owns data/model (the in-pod
            # reduce-scatter = the paper's ONU aggregation step)
            if microbatches == 1:
                (tot, cnt), grads = ugrad(params, batch)
            else:
                def split(x):
                    return x.reshape((microbatches, x.shape[0] // microbatches)
                                     + x.shape[1:])
                mb = jax.tree.map(split, batch)

                def body(carry, mbi):
                    acc, t_a, c_a = carry
                    (t, c), g = ugrad(params, mbi)
                    acc = jax.tree.map(
                        lambda a, gi: a + gi.astype(jnp.float32), acc, g)
                    return (acc, t_a + t, c_a + c), 0.0

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (grads, tot, cnt), _ = jax.lax.scan(
                    body, (zeros, jnp.zeros((), jnp.float32),
                           jnp.zeros((), jnp.float32)), mb)
            # CPS step: int8 stochastic-rounding cross-pod sum (the paper's
            # constant-upstream hop, compressed 2x vs bf16 / 4x vs f32)
            leaves, treedef = jax.tree.flatten(grads)
            keys = jax.random.split(key, len(leaves))
            summed = []
            for leaf, k in zip(leaves, keys):
                lf = leaf.astype(jnp.float32)
                scale = jnp.maximum(jnp.max(jnp.abs(lf)), 1e-12) / 127.0
                noise = jax.random.uniform(k, lf.shape, jnp.float32) - 0.5
                q = jnp.clip(jnp.round(lf / scale + noise), -127, 127
                             ).astype(jnp.int8)
                q_all = jax.lax.all_gather(q, "pod")
                s_all = jax.lax.all_gather(scale, "pod")
                summed.append(jnp.tensordot(
                    s_all, q_all.astype(jnp.float32), axes=(0, 0)))
            grads = jax.tree.unflatten(treedef, summed)
            K = jax.lax.psum(cnt, "pod")
            grads = jax.tree.map(lambda g: g / jnp.maximum(K, 1e-6), grads)
            loss = jax.lax.psum(tot, "pod") / jnp.maximum(K, 1e-6)
            new_params, new_state = opt.update(params, grads, opt_state, lr)
            return new_params, new_state, loss

        def train_step(params, opt_state, batch, key=None):
            if key is None:
                # derive a fresh per-step key from the run seed and the
                # optimizer's step counter — a fixed key would repeat the
                # same stochastic-rounding noise every step (and across
                # seed replicas), biasing the compressed sum
                t = opt_state.get("t") if isinstance(opt_state, dict) else None
                if t is None:
                    raise ValueError(
                        "two_step_int8 with a stateless optimizer needs an "
                        "explicit key= per step (no step counter to derive "
                        "fresh stochastic-rounding noise from)")
                key = jax.random.fold_in(jax.random.PRNGKey(seed), t)
            bspecs = jax.tree.map(lambda _: P("pod"), batch)
            pspecs = jax.tree.map(lambda _: P(), params)
            ospecs = jax.tree.map(lambda _: P(), opt_state)
            fn = jax.shard_map(
                pod_body, mesh=mesh,
                in_specs=(pspecs, ospecs, bspecs, P()),
                out_specs=(pspecs, ospecs, P()),
                axis_names={"pod"},
                # outputs ARE pod-invariant (identical all-gathered sums on
                # every pod); the varying-axes checker can't see through the
                # dequant-tensordot
                check_vma=False)
            return fn(params, opt_state, batch, key)

        return train_step

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            (loss, _), grads = grad_fn(params, batch, cfg, rules)
        else:
            def split(x):
                return x.reshape((microbatches, x.shape[0] // microbatches)
                                 + x.shape[1:])
            mb = jax.tree.map(split, batch)

            def body(acc, mbi):
                (l, _), g = grad_fn(params, mbi, cfg, rules)
                acc = jax.tree.map(
                    lambda a, gi: a + gi.astype(jnp.float32), acc, g)
                return acc, l

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, losses = jax.lax.scan(body, zeros, mb)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = jnp.mean(losses)
        new_params, new_state = opt.update(params, grads, opt_state, lr)
        return new_params, new_state, loss

    return train_step


def make_prefill_step(cfg: ModelConfig, rules: ShardingRules, cache_len: int):
    def prefill_step(params, batch):
        return transformer.prefill(params, batch, cfg, rules, cache_len)
    return prefill_step


def make_serve_step(cfg: ModelConfig, rules: ShardingRules):
    def serve_step(params, batch, cache):
        return transformer.decode_step(params, batch, cache, cfg, rules)
    return serve_step


def input_specs(cfg: ModelConfig, shp: ShapeConfig, mesh: Mesh,
                rules: ShardingRules, opt_name: str = "adamw",
                microbatches: int = 1, transport: str = "gspmd"):
    """Everything the dry-run needs for one cell: (fn, args, out_shardings)."""
    params_abs, params_shard = param_shardings(cfg, mesh, rules)
    if shp.kind == "train":
        batch = batch_struct(cfg, shp, mesh, rules)
        opt_abs = opt_state_struct(opt_name, params_abs)
        fn = make_train_step(cfg, rules, opt_name, microbatches=microbatches,
                             transport=transport, mesh=mesh)
        return fn, (params_abs, opt_abs, batch), None
    if shp.kind == "prefill":
        batch = batch_struct(cfg, shp, mesh, rules)
        batch.pop("client_weight", None)
        fn = make_prefill_step(cfg, rules, cache_len=shp.seq_len)
        return fn, (params_abs, batch), None
    if shp.kind == "decode":
        batch = batch_struct(cfg, shp, mesh, rules, decode=True)
        cache = cache_struct_sharded(cfg, shp, mesh, rules)
        fn = make_serve_step(cfg, rules)
        return fn, (params_abs, batch, cache), None
    raise ValueError(shp.kind)
