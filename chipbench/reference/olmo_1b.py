"""Plain reference of OLMo-1B and of one step of the federated gradient
round (OLMo: Groeneveld et al., arXiv:2402.00838, Table 1; allenai/OLMo-1B
``config.json``).

The model, written from the paper and the published configuration in
``jax.numpy`` with nothing imported from the program: tokens -> the
embedding table (no scale) -> ``n_layers`` sequential blocks, each
``x + attn(LN(x))`` then ``x + mlp(LN(x))`` -> LN -> logits against the same
table (weight tying). LN is LayerNorm with no parameters. Attention is
causal multi-head attention over ``att_proj`` (one fused q, k, v
projection), rotary embeddings (the rotate-half form, theta from the
configuration) on q and k, then ``attn_out``. The MLP is SwiGLU over the
fused ``ff_proj``: its first half is the value, its second the gate,
``silu(gate) * value``, then ``ff_out``. No biases, no dropout.

The round (the program's gradient regime, H = 1): each row is one selected
client's sequence with its weight k (0 for a client the PON did not
involve); the loss is the weighted token mean sum(k * nll) / sum(k) over
every position that has a next token, its gradient is the round's
aggregate, and AdamW takes one step.

Departures from OLMo's own training, each the program's (so the
comparison holds the program to its own rule): AdamW with beta 0.9/0.95,
eps 1e-8, no weight decay, no gradient clipping, a constant learning rate
(OLMo: eps 1e-5, weight decay 0.1, clipping at 1.0, warm-up and decay);
the loss weighted by client; every weight drawn normal with std 0.02,
OLMo-1B's ``init_std``, where OLMo scales some by depth.

The parameters are kept in the configuration's dtype (bfloat16), as
the program keeps them: each AdamW step is computed in the compute dtype
and its result rounded to the parameters' dtype. Everything else is
computed in the compute dtype: float32 (under "highest" matmul precision)
is the reference; bfloat16 throughout, layer norm statistics, softmax,
loss and AdamW moments included, is the control.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

INIT_STD = 0.02
LAYERS = ("att_proj", "attn_out", "ff_proj", "ff_out")
ADAMW = {"b1": 0.9, "b2": 0.95, "eps": 1e-8}


def dims(widths: dict) -> dict:
    d, h = widths["d_model"], widths["n_heads"]
    return {"L": widths["n_layers"], "d": d, "H": h, "hd": d // h,
            "ff": widths["mlp_ratio"] * d // 2,
            "V": widths["embedding_size"]}


def shapes(widths: dict) -> dict:
    k = dims(widths)
    L, d, ff = k["L"], k["d"], k["ff"]
    return {"wte": (k["V"], d), "att_proj": (L, d, 3 * d),
            "attn_out": (L, d, d), "ff_proj": (L, d, 2 * ff),
            "ff_out": (L, ff, d)}


def init(widths: dict, key, dtype=jnp.bfloat16) -> dict:
    """Every weight normal with std ``INIT_STD``, rounded to ``dtype``.
    Call under ``jax.jit`` to make them on the device in one program."""
    sh = shapes(widths)
    keys = jax.random.split(key, len(sh))
    return {n: (jax.random.normal(k, sh[n], jnp.float32) * INIT_STD
                ).astype(dtype) for k, n in zip(keys, sorted(sh))}


def _layer_norm(x, eps: float):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype))


def _rotary(x, theta: float):
    """x: (B, S, H, hd); rotate-half rotary embedding at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[3]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _block(x, p, widths: dict):
    k = dims(widths)
    B, S, d = x.shape
    H, hd = k["H"], k["hd"]
    eps, theta = widths["layer_norm_eps"], widths["rope_theta"]
    h = _layer_norm(x, eps)
    q, kk, v = jnp.split(h @ p["att_proj"], 3, axis=-1)
    q = _rotary(q.reshape(B, S, H, hd), theta)
    kk = _rotary(kk.reshape(B, S, H, hd), theta)
    v = v.reshape(B, S, H, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / jnp.asarray(math.sqrt(hd),
                                                            x.dtype)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, jnp.finfo(x.dtype).min)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, S, d)
    x = x + o @ p["attn_out"]
    h = _layer_norm(x, eps)
    val, gate = jnp.split(h @ p["ff_proj"], 2, axis=-1)
    return x + (jax.nn.silu(gate) * val) @ p["ff_out"]


def weighted_nll(params: dict, tokens, weights, widths: dict,
                 remat: bool = True, rows: Optional[NamedSharding] = None):
    """sum over rows r and positions t < S-1 of weights[r] * nll(token
    t+1 | tokens <= t), in the parameters' dtype. ``remat`` keeps only
    each block's input for the backward pass; ``rows`` pins activations
    to a row sharding."""
    pin = (lambda x: x) if rows is None else (
        lambda x: jax.lax.with_sharding_constraint(x, rows))
    block = functools.partial(_block, widths=widths)
    if remat:
        block = jax.checkpoint(block)

    def body(x, p):
        return pin(block(x, p)), None

    x = pin(params["wte"][tokens])
    x, _ = jax.lax.scan(body, x, {n: params[n] for n in LAYERS})
    x = _layer_norm(x, widths["layer_norm_eps"])
    logp = jax.nn.log_softmax(x[:, :-1] @ params["wte"].T, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(nll * weights[:, None].astype(nll.dtype))


@jax.jit
def _square_sums(tree: dict) -> dict:
    return {n: (jnp.sum(jnp.square(x.astype(jnp.float32))) if n == "wte"
                else jnp.sum(jnp.square(x.astype(jnp.float32)),
                             axis=tuple(range(1, x.ndim))))
            for n, x in tree.items()}


def layer_norms(tree: dict) -> Dict[str, float]:
    """The table's norm under ``wte`` and each layer's under
    ``<name>.<layer>``, summed in float32 on the device."""
    out = {}
    for n, v in _square_sums(tree).items():
        v = np.asarray(v, np.float64)
        if n == "wte":
            out[n] = math.sqrt(float(v))
        else:
            out.update({f"{n}.{i}": math.sqrt(float(x))
                        for i, x in enumerate(v)})
    return out


@jax.jit
def _moment(g: dict, first: dict, scale):
    """(1 - b1) * (g - 2 * first) * scale: AdamW's first moment after one
    step from the gradient sum ``g`` with ``first`` negated in it."""
    return jax.tree.map(lambda x, y: ((1 - ADAMW["b1"]) * (x - 2.0 * y)
                                      * scale.astype(x.dtype)), g, first)


@jax.jit
def _change(a: dict, b: dict):
    return jax.tree.map(lambda x, y: (x.astype(jnp.float32)
                                      - y.astype(jnp.float32)), a, b)


class Trainer:
    """The model's state and one AdamW step per round, computed in
    ``dtype``, the parameters kept in ``param_dtype`` (``dtype`` when
    None).

    The state is spread over ``devices`` (each weight split along an axis
    the device count divides); rows run in blocks of one per device, each
    block's gradient added to the round's in ``dtype``."""

    def __init__(self, widths: dict, params: dict, lr: float,
                 devices: Sequence, dtype=jnp.float32,
                 precision: str = "highest", param_dtype=None):
        self.precision = precision
        param_dtype = dtype if param_dtype is None else param_dtype
        self.n = len(devices)
        mesh = Mesh(np.array(devices), ("x",))
        self.state_sharding = {n: NamedSharding(mesh, self._spec(n, x.shape))
                               for n, x in params.items()}
        self.row_sharding = NamedSharding(mesh, P("x"))
        act = NamedSharding(mesh, P("x", None, None))
        to = lambda t, dt: jax.tree.map(lambda x: x.astype(dt), t)
        self.params = jax.jit(lambda t: to(t, param_dtype),
                              out_shardings=self.state_sharding)(params)
        zeros = jax.jit(lambda t: jax.tree.map(
            lambda x: jnp.zeros(x.shape, dtype), t),
            out_shardings=self.state_sharding)
        self._zeros = lambda: zeros(self.params)
        self.m, self.v, self.t = self._zeros(), self._zeros(), 0

        def add_grad(params, acc, tokens, weights):
            num, g = jax.value_and_grad(weighted_nll)(
                to(params, dtype), tokens, weights, widths, rows=act)
            return jax.tree.map(jnp.add, acc, g), num

        self._add_grad = jax.jit(add_grad, donate_argnums=1,
                                 out_shardings=(self.state_sharding, None))

        def adamw(p, g, m, v, scale, t):
            b1, b2, eps = ADAMW["b1"], ADAMW["b2"], ADAMW["eps"]
            bc1 = (1.0 - b1 ** t).astype(dtype)
            bc2 = (1.0 - b2 ** t).astype(dtype)
            new = {}
            for n in p:
                gn = g[n] * scale.astype(dtype)
                mn = b1 * m[n] + (1 - b1) * gn
                vn = b2 * v[n] + (1 - b2) * jnp.square(gn)
                step = (mn / bc1) / (jnp.sqrt(vn / bc2) + eps)
                new[n] = ((p[n].astype(dtype) - lr * step
                           ).astype(param_dtype), mn, vn)
            return tuple({n: new[n][i] for n in p} for i in range(3))

        self._adamw = jax.jit(adamw, donate_argnums=(0, 2, 3),
                              out_shardings=(self.state_sharding,) * 3)

    def _spec(self, name: str, shape) -> P:
        axis = 0 if name == "wte" else 1
        if shape[axis] % self.n:
            return P()
        return P(*([None] * axis + ["x"]))

    def gradient(self, tokens: np.ndarray, weights: np.ndarray):
        """(sum of weighted nll, its gradient) over all rows."""
        acc, num = self._zeros(), 0.0
        with jax.default_matmul_precision(self.precision):
            for lo in range(0, len(tokens), self.n):
                tk = jax.device_put(tokens[lo:lo + self.n],
                                    self.row_sharding)
                w = jax.device_put(np.asarray(weights[lo:lo + self.n],
                                              np.float32), self.row_sharding)
                acc, part = self._add_grad(self.params, acc, tk, w)
                num += float(part)
        return num, acc

    def step(self, tokens: np.ndarray, weights: np.ndarray,
             faults: bool = False) -> dict:
        """One round: the weighted loss before the step, AdamW's first
        moment after it (per-layer norms), and with ``faults`` the first
        moment each planted fault would give instead."""
        den = float(np.sum(weights, dtype=np.float64)) * (tokens.shape[1] - 1)
        num, g = self.gradient(tokens, weights)
        out = {"loss": num / den}
        if faults:
            out["faults"] = self._faults(tokens, weights, g, den)
        self.t += 1
        with jax.default_matmul_precision(self.precision):
            self.params, self.m, self.v = self._adamw(
                self.params, g, self.m, self.v,
                jnp.asarray(1.0 / den, jnp.float32),
                jnp.asarray(self.t, jnp.float32))
        out["moment_norms"] = layer_norms(self.m)
        return out

    def _faults(self, tokens, weights, g, den) -> dict:
        """First-moment norms under each planted fault: ``half``, the
        gradient of the first half of the weighted rows alone;
        ``answer``, the first weighted row's weight negated; ``unchanged``,
        no gradient at all."""
        live = np.flatnonzero(weights > 0)
        half = np.zeros_like(weights)
        half[live[:len(live) // 2]] = weights[live[:len(live) // 2]]
        _, g_half = self.gradient(tokens, half)
        first = np.zeros_like(weights)
        first[live[0]] = weights[live[0]]
        r = live[0] // self.n * self.n
        _, g_first = self.gradient(tokens[r:r + self.n],
                                   first[r:r + self.n])
        den_half = float(np.sum(half, dtype=np.float64)) * (
            tokens.shape[1] - 1)
        den_ans = den - 2.0 * float(weights[live[0]]) * (tokens.shape[1] - 1)
        none = jax.tree.map(jnp.zeros_like, g_half)
        out = {"half": layer_norms(_moment(g_half, none,
                                           jnp.float32(1.0 / den_half))),
               "answer": layer_norms(_moment(g, g_first,
                                             jnp.float32(1.0 / den_ans)))}
        out["unchanged"] = {n: 0.0 for n in out["half"]}
        return out

    def change_norms(self, start: dict) -> Dict[str, float]:
        """Per-layer norms of the parameters' change from ``start``."""
        return layer_norms(_change(self.params, start))
