"""RWKV6 ("Finch") — linear attention with data-dependent per-channel decay.

Recurrence per head (state S ∈ R^{hd×hd}):
    o_t = r_t · (S_{t-1} + (u ⊙ k_t) ⊗ v_t)
    S_t = diag(w_t) · S_{t-1} + k_t ⊗ v_t
with w_t = exp(-exp(ww_t)) data-dependent (LoRA on the shifted input).

Train/prefill use the chunkwise-parallel form (chunk size cfg.rwkv_chunk):
within-chunk pair interactions use the numerically-safe decay-difference
tensor (all exponents ≤ 0), cross-chunk state flows through a scan.

The Pallas kernel in repro/kernels/rwkv6_scan.py implements the same
chunk body with VMEM tiling; repro/kernels/ref.py's oracle is the exact
sequential recurrence this module is tested against.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.sharding import ShardingRules, constrain, pad_to_multiple
from repro.models.layers import group_rmsnorm


def rwkv_heads(cfg, tp: int = 16):
    H = cfg.d_model // cfg.rwkv_head_dim
    Hp = pad_to_multiple(H, tp) if cfg.tp_pad_heads else H
    return H, Hp


_STREAMS = ("r", "k", "v", "w", "g")


def rwkv_time_params(pb, cfg, name: str = "time"):
    d, hd, lora = cfg.d_model, cfg.rwkv_head_dim, cfg.rwkv_lora
    H, Hp = rwkv_heads(cfg)
    D = Hp * hd
    sub = pb.sub(name)
    sub.param("mu_base", (d,), ("embed",), init="uniform", scale=0.5)
    sub.param("lora_a", (d, lora), ("embed", "lora"), scale=0.5)
    for s in _STREAMS:
        sub.param(f"mu_{s}", (d,), ("embed",), init="uniform", scale=0.5)
        sub.param(f"lora_b_{s}", (lora, d), ("lora", "embed"), init="zeros")
    sub.param("wr", (d, D), ("embed", "mlp"))
    sub.param("wk", (d, D), ("embed", "mlp"))
    sub.param("wv", (d, D), ("embed", "mlp"))
    sub.param("wg", (d, D), ("embed", "mlp"))
    sub.param("wo", (D, d), ("mlp", "embed"))
    sub.param("decay_base", (D,), ("mlp",), init="linspace", scale=1.5)
    sub.param("decay_a", (d, lora), ("embed", "lora"), scale=0.5)
    sub.param("decay_b", (lora, D), ("lora", "mlp"), init="zeros")
    sub.param("bonus_u", (Hp, hd), ("heads", "head_dim"), init="uniform", scale=0.5)
    sub.param("ln_out", (Hp * hd,), ("mlp",), init="ones")


def rwkv_channel_params(pb, cfg, name: str = "channel"):
    d, ff = cfg.d_model, cfg.d_ff
    sub = pb.sub(name)
    sub.param("mu_k", (d,), ("embed",), init="uniform", scale=0.5)
    sub.param("mu_r", (d,), ("embed",), init="uniform", scale=0.5)
    sub.param("wk", (d, ff), ("embed", "mlp"))
    sub.param("wv", (ff, d), ("mlp", "embed"))
    sub.param("wr", (d, d), ("embed", None), scale=0.5)


def _token_shift(x, x_prev_last: Optional[jax.Array]):
    """x_{t-1} along the sequence; x_prev_last (B, d) carries across chunks."""
    B, S, d = x.shape
    if x_prev_last is None:
        x_prev_last = jnp.zeros((B, d), x.dtype)
    return jnp.concatenate([x_prev_last[:, None, :], x[:, :-1, :]], axis=1)


def _ddlerp(x, xp, p, stream: str):
    """RWKV6 data-dependent lerp between x_t and x_{t-1}."""
    base = x + (xp - x) * p["mu_base"]
    lora = jnp.tanh(jnp.einsum("bsd,dl->bsl", base, p["lora_a"]))
    mix = p[f"mu_{stream}"] + jnp.einsum("bsl,ld->bsd", lora, p[f"lora_b_{stream}"])
    return x + (xp - x) * mix


def _project_heads(x, w, Hp, hd):
    y = jnp.einsum("bsd,de->bse", x, w)
    return y.reshape(x.shape[0], x.shape[1], Hp, hd)


def _chunk_body(r, k, v, logw, u, S_in, head_mask):
    """One chunk of the wkv recurrence for all heads.

    r,k,v: (B, W, H, hd); logw: (B, W, H, hd) (≤ 0); S_in: (B, H, hd, hd).
    Returns (o (B,W,H,hd), S_out).
    """
    B, W, H, hd = r.shape
    r = r.astype(jnp.float32)
    k = k.astype(jnp.float32)
    v = v.astype(jnp.float32)
    c = jnp.cumsum(logw, axis=1)                         # inclusive Σ log w
    c_excl = c - logw                                     # exclusive (= c_{t-1})
    # cross-chunk: o += (r_t ⊙ exp(c_{t-1})) @ S_in
    r_dec = r * jnp.exp(c_excl)
    o = jnp.einsum("bwhk,bhkv->bwhv", r_dec, S_in)
    # intra-chunk pairs j < t; exponent c_excl[t] - c[j] ≤ 0 for the causal
    # pairs — clamp at 0 so the masked (acausal) pairs cannot overflow
    diff = c_excl[:, :, None] - c[:, None, :, :]          # (B, T=W, J=W, H, hd)
    pair = r[:, :, None] * k[:, None, :, :] * jnp.exp(jnp.minimum(diff, 0.0))
    att = jnp.sum(pair, axis=-1)                          # (B, T, J, H)
    tri = jnp.tril(jnp.ones((W, W), bool), k=-1)
    att = jnp.where(tri[None, :, :, None], att, 0.0)
    # diagonal bonus term: (r_t · (u ⊙ k_t)) v_t
    diag = jnp.sum(r * (u[None, None] * k), axis=-1)      # (B, W, H)
    o = o + jnp.einsum("btjh,bjhv->bthv", att, v) + diag[..., None] * v
    # state update: S_out = S_in ⊙ exp(c_W) + Σ_j (k_j ⊙ exp(c_W - c_j)) ⊗ v_j
    c_tot = c[:, -1]                                      # (B, H, hd)
    k_dec = k * jnp.exp(c_tot[:, None] - c)
    S_out = S_in * jnp.exp(c_tot)[..., None] + jnp.einsum("bjhk,bjhv->bhkv", k_dec, v)
    if head_mask is not None:
        o = o * head_mask
    return o, S_out


def rwkv_time_mix(x, p, cfg, rules: ShardingRules, state=None):
    """Time-mix sublayer. state: None (train) or
    {'S': (B,H,hd,hd) f32, 'shift': (B,d)} for decode/chunked prefill."""
    B, S, d = x.shape
    hd = cfg.rwkv_head_dim
    H, Hp = rwkv_heads(cfg)
    head_mask = None
    if Hp != H:
        head_mask = (jnp.arange(Hp) < H).astype(jnp.float32)[None, None, :, None]

    xp = _token_shift(x, None if state is None else state["shift"])
    xr = _ddlerp(x, xp, p, "r")
    xk = _ddlerp(x, xp, p, "k")
    xv = _ddlerp(x, xp, p, "v")
    xw = _ddlerp(x, xp, p, "w")
    xg = _ddlerp(x, xp, p, "g")

    r = _project_heads(xr, p["wr"], Hp, hd)
    k = _project_heads(xk, p["wk"], Hp, hd)
    v = _project_heads(xv, p["wv"], Hp, hd)
    g = jax.nn.silu(jnp.einsum("bsd,de->bse", xg, p["wg"]).astype(jnp.float32))
    r = constrain(r, rules, ("batch", "seq", "heads", None))

    ww = p["decay_base"].astype(jnp.float32) + jnp.einsum(
        "bsl,le->bse",
        jnp.tanh(jnp.einsum("bsd,dl->bsl", xw, p["decay_a"])).astype(jnp.float32),
        p["decay_b"].astype(jnp.float32),
    )
    # log w = -exp(ww)  (clamped for chunk numerics; w ∈ (~e^-20, 1))
    logw = -jnp.exp(jnp.clip(ww, -8.0, 3.0)).reshape(B, S, Hp, hd)
    u = p["bonus_u"].astype(jnp.float32)

    S0 = jnp.zeros((B, Hp, hd, hd), jnp.float32) if state is None else state["S"]
    W = min(cfg.rwkv_chunk, S)
    if S % W:
        W = S  # odd lengths (tests, ragged tails): single chunk
    assert S % W == 0, (S, W)
    n_chunks = S // W

    def split(t):
        return t.reshape(B, n_chunks, W, Hp, hd)

    rc, kc, vc, wc = split(r), split(k), split(v), split(logw)
    if n_chunks == 1:
        outs, St = [], S0
        for i in range(n_chunks):
            o, St = _chunk_body(rc[:, i], kc[:, i], vc[:, i], wc[:, i], u, St, head_mask)
            outs.append(o)
        o = jnp.stack(outs, axis=1)
    else:
        def body(St, chunk):
            ri, ki, vi, wi = chunk
            o, St = _chunk_body(ri, ki, vi, wi, u, St, head_mask)
            return St, o
        St, o = jax.lax.scan(
            body, S0,
            (rc.transpose(1, 0, 2, 3, 4), kc.transpose(1, 0, 2, 3, 4),
             vc.transpose(1, 0, 2, 3, 4), wc.transpose(1, 0, 2, 3, 4)))
        o = o.transpose(1, 0, 2, 3, 4)
    o = o.reshape(B, S, Hp, hd).astype(x.dtype)
    o = group_rmsnorm(o, p["ln_out"].reshape(Hp, hd), Hp).reshape(B, S, Hp * hd)
    o = (o.astype(jnp.float32) * g).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", o, p["wo"])
    out = constrain(out, rules, ("batch", "seq", "embed"))
    new_state = {"S": St, "shift": x[:, -1, :]}
    return out, new_state


def rwkv_channel_mix(x, p, cfg, rules: ShardingRules, state=None):
    xp = _token_shift(x, None if state is None else state["shift"])
    xk = x + (xp - x) * p["mu_k"]
    xr = x + (xp - x) * p["mu_r"]
    k = jnp.einsum("bsd,df->bsf", xk, p["wk"])
    k = constrain(k, rules, ("batch", "seq", "mlp"))
    k = jnp.square(jax.nn.relu(k.astype(jnp.float32))).astype(x.dtype)
    rgate = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", xr, p["wr"]).astype(jnp.float32))
    out = jnp.einsum("bsf,fd->bsd", k, p["wv"])
    out = (out.astype(jnp.float32) * rgate).astype(x.dtype)
    return constrain(out, rules, ("batch", "seq", "embed")), {"shift": x[:, -1, :]}


def rwkv_init_state(cfg, batch: int, dtype):
    hd = cfg.rwkv_head_dim
    _, Hp = rwkv_heads(cfg)
    return {
        "time": {"S": jnp.zeros((batch, Hp, hd, hd), jnp.float32),
                 "shift": jnp.zeros((batch, cfg.d_model), dtype)},
        "channel": {"shift": jnp.zeros((batch, cfg.d_model), dtype)},
    }


def rwkv_state_abstract(cfg, batch: int, dtype):
    hd = cfg.rwkv_head_dim
    _, Hp = rwkv_heads(cfg)
    return {
        "time": {"S": jax.ShapeDtypeStruct((batch, Hp, hd, hd), jnp.float32),
                 "shift": jax.ShapeDtypeStruct((batch, cfg.d_model), dtype)},
        "channel": {"shift": jax.ShapeDtypeStruct((batch, cfg.d_model), dtype)},
    }
