"""Optimizers (no optax offline): SGD(+momentum), AdamW, server optimizers.

State layout mirrors params (pytrees); everything fp32 master with bf16
compute params, matching the mixed-precision policy in launch/train.py.
The FedAvg *server* optimizer treats the aggregated client delta as a
pseudo-gradient (Reddi et al., FedOpt) — ``server='sgd'`` with lr=1 is
vanilla FedAvg; ``server='adam'`` is FedAdam.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp


def cosine_lr(base: float, warmup: int, total: int):
    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = base * step / jnp.maximum(1.0, warmup)
        prog = jnp.clip((step - warmup) / jnp.maximum(1.0, total - warmup), 0.0, 1.0)
        cos = base * 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
        return jnp.where(step < warmup, warm, cos)
    return lr


# --- SGD ----------------------------------------------------------------

def sgd_init(params, momentum: float = 0.0):
    if momentum:
        return {"mu": jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), params)}
    return {}


def sgd_update(params, grads, state, lr, momentum: float = 0.0,
               weight_decay: float = 0.0):
    def upd(p, g, m):
        gf = g.astype(jnp.float32)
        if weight_decay:
            gf = gf + weight_decay * p.astype(jnp.float32)
        if momentum:
            m = momentum * m + gf
            gf = m
        return (p.astype(jnp.float32) - lr * gf).astype(p.dtype), m
    if momentum:
        out = jax.tree.map(upd, params, grads, state["mu"])
        new_p = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
        new_m = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
        return new_p, {"mu": new_m}
    new_p = jax.tree.map(lambda p, g: upd(p, g, None)[0], params, grads)
    return new_p, state


# --- AdamW ---------------------------------------------------------------

def adamw_init(params):
    # zeros_like: a moment is born where its parameter lives (sharded
    # params get sharded moments, not one unsharded copy on device 0)
    z = lambda x: jnp.zeros_like(x, jnp.float32)
    return {
        "m": jax.tree.map(z, params),
        "v": jax.tree.map(z, params),
        "t": jnp.zeros((), jnp.int32),
    }


def adamw_update(params, grads, state, lr, b1=0.9, b2=0.95, eps=1e-8,
                 weight_decay: float = 0.0):
    t = state["t"] + 1
    bc1 = 1.0 - b1 ** t.astype(jnp.float32)
    bc2 = 1.0 - b2 ** t.astype(jnp.float32)

    def upd(p, g, m, v):
        gf = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * gf
        v = b2 * v + (1 - b2) * jnp.square(gf)
        step = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
        if weight_decay:
            step = step + weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * step).astype(p.dtype), m, v

    out = jax.tree.map(upd, params, grads, state["m"], state["v"])
    is3 = lambda x: isinstance(x, tuple)
    return (jax.tree.map(lambda o: o[0], out, is_leaf=is3),
            {"m": jax.tree.map(lambda o: o[1], out, is_leaf=is3),
             "v": jax.tree.map(lambda o: o[2], out, is_leaf=is3),
             "t": t})


# --- Yogi ----------------------------------------------------------------

def yogi_init(params):
    return adamw_init(params)


def yogi_update(params, grads, state, lr, b1=0.9, b2=0.99, eps=1e-3,
                weight_decay: float = 0.0):
    """Yogi (Zaheer et al. 2018) — the FedYogi server rule in Reddi et al.

    Differs from Adam only in the second-moment update: additive with a
    sign, v ← v − (1−b2)·sign(v − g²)·g², so v can shrink at a controlled
    rate when the pseudo-gradient variance drops between rounds.
    """
    t = state["t"] + 1
    bc1 = 1.0 - b1 ** t.astype(jnp.float32)
    bc2 = 1.0 - b2 ** t.astype(jnp.float32)

    def upd(p, g, m, v):
        gf = g.astype(jnp.float32)
        g2 = jnp.square(gf)
        m = b1 * m + (1 - b1) * gf
        v = v - (1 - b2) * jnp.sign(v - g2) * g2
        step = (m / bc1) / (jnp.sqrt(jnp.maximum(v, 0.0) / bc2) + eps)
        if weight_decay:
            step = step + weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * step).astype(p.dtype), m, v

    out = jax.tree.map(upd, params, grads, state["m"], state["v"])
    is3 = lambda x: isinstance(x, tuple)
    return (jax.tree.map(lambda o: o[0], out, is_leaf=is3),
            {"m": jax.tree.map(lambda o: o[1], out, is_leaf=is3),
             "v": jax.tree.map(lambda o: o[2], out, is_leaf=is3),
             "t": t})


# --- dispatcher ----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Optimizer:
    name: str
    init: Callable
    update: Callable    # (params, grads, state, lr) -> (params, state)


def make_optimizer(name: str, momentum: float = 0.9,
                   weight_decay: float = 0.0) -> Optimizer:
    if name == "sgd":
        return Optimizer(
            "sgd",
            lambda p: sgd_init(p, 0.0),
            lambda p, g, s, lr: sgd_update(p, g, s, lr, 0.0, weight_decay))
    if name == "sgdm":
        return Optimizer(
            "sgdm",
            lambda p: sgd_init(p, momentum),
            lambda p, g, s, lr: sgd_update(p, g, s, lr, momentum, weight_decay))
    if name == "adamw":
        return Optimizer(
            "adamw",
            adamw_init,
            lambda p, g, s, lr: adamw_update(p, g, s, lr, weight_decay=weight_decay))
    if name == "yogi":
        return Optimizer(
            "yogi",
            yogi_init,
            lambda p, g, s, lr: yogi_update(p, g, s, lr, weight_decay=weight_decay))
    raise ValueError(name)
