"""Core neural layers: norms, RoPE, chunked causal attention (GQA / MQA /
sliding-window / cross), dense layers and MLPs.

All functions are pure; parameters come in as dicts built by ParamBuilder.
Training attention is a ``lax.scan`` over query blocks of ``q_chunk`` rows,
each block attending to the full KV under a causal (and, with a window,
sliding) mask: compact HLO inside the scanned-over-layers program, at the
cost of the masked-away FLOPs above the diagonal.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.sharding import constrain, pad_to_multiple


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def norm(x, p, kind: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rms":
        y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
        y = y * (1.0 + p["scale"].astype(jnp.float32))
    elif kind == "ln":
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * (1.0 + p["scale"].astype(jnp.float32)) + p["bias"].astype(jnp.float32)
    elif kind == "nonparam":  # olmo: LayerNorm without learnable params
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    else:
        raise ValueError(kind)
    return y.astype(x.dtype)


def norm_params(pb, name: str, d: int, kind: str):
    sub = pb.sub(name)
    if kind == "rms":
        sub.param("scale", (d,), ("embed",), init="zeros")
    elif kind == "ln":
        sub.param("scale", (d,), ("embed",), init="zeros")
        sub.param("bias", (d,), ("embed",), init="zeros")
    # nonparam: no params
    return sub


def group_rmsnorm(x, weight, n_heads: int, eps: float = 1e-6):
    """Per-head RMS norm over the trailing head_dim (RWKV output norm)."""
    B, S, H, hd = x.shape
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32).reshape(1, 1, H, hd)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float):
    return theta ** (-np.arange(0, head_dim, 2, dtype=np.float32) / head_dim)


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: (..., S) int32 absolute positions."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(hd, theta))  # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]  # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attn_params(pb, cfg, tp: int = 16):
    """QKV(+bias) + output projection with query-head padding to the TP size.

    Padded query heads are zero-init and their outputs are masked, so the
    function is exactly the unpadded model's (and stays that way: masked
    outputs stop gradients into pad heads).

    KV placement: replicated across the tensor axis for GQA (small); for
    MHA archs whose head count divides the TP size (musicgen, olmo) the KV
    heads shard over 'model' — replicating them costs a full extra d² of
    per-token compute per TP rank (useful-FLOPs ratio 0.28 → ~0.8).
    """
    d, hd, H, KV = cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    Hp = pad_to_multiple(H, tp) if cfg.tp_pad_heads else H
    shard_kv = (cfg.shard_kv_mha and KV == H == Hp and H % tp == 0)
    kv_ax = "heads" if shard_kv else "kv_heads"
    sub = pb.sub("attn")
    sub.param("wq", (d, Hp, hd), ("embed", "heads", "head_dim"))
    sub.param("wk", (d, KV, hd), ("embed", kv_ax, "head_dim"))
    sub.param("wv", (d, KV, hd), ("embed", kv_ax, "head_dim"))
    sub.param("wo", (Hp, hd, d), ("heads", "head_dim", "embed"))
    if cfg.qkv_bias:
        sub.param("bq", (Hp, hd), ("heads", "head_dim"), init="zeros")
        sub.param("bk", (KV, hd), (kv_ax, "head_dim"), init="zeros")
        sub.param("bv", (KV, hd), (kv_ax, "head_dim"), init="zeros")
    return Hp


def _qkv(x, p, cfg, rules, Hp):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = constrain(q, rules, ("batch", "seq", "heads", None))
    return q, k, v


def _head_mask(Hp: int, H: int, dtype):
    if Hp == H:
        return None
    return (jnp.arange(Hp) < H).astype(dtype)[None, None, :, None]


def _expand_kv(k, Hp: int, H: int, KV: int):
    """Map KV heads onto (padded) query heads: static gather, no copy cost
    after XLA fuses the broadcast."""
    group = np.minimum(np.arange(Hp) // max(1, H // KV), KV - 1)
    return k[:, :, group, :]


def _attend_block(q_blk, k_ctx, v_ctx, mask, scale, softcap=0.0):
    """One query block against a KV context. q_blk (B,C,H,hd)."""
    logits = jnp.einsum("bqhk,bshk->bhqs", q_blk, k_ctx).astype(jnp.float32) * scale
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqs,bshk->bqhk", probs.astype(v_ctx.dtype), v_ctx)


def causal_attention(q, k, v, cfg, rules, *, window: int = 0):
    """Chunked causal (optionally sliding-window) attention.

    q (B,S,Hp,hd); k,v (B,S,KV,hd). Returns (B,S,Hp,hd).
    """
    B, S, Hp, hd = q.shape
    H, KV = cfg.n_heads, cfg.n_kv_heads
    scale = 1.0 / np.sqrt(hd)
    kf = _expand_kv(k, Hp, H, KV)
    vf = _expand_kv(v, Hp, H, KV)
    C = min(cfg.q_chunk, S)
    if S % C:
        C = S  # odd lengths (tests, ragged tails): single block
    n_blk = S // C
    assert S % C == 0, (S, C)
    span = jnp.arange(C)

    qr = q.reshape(B, n_blk, C, Hp, hd).transpose(1, 0, 2, 3, 4)
    kpos = jnp.arange(S)

    def body(_, blk):
        i, qi = blk
        qpos = i * C + span
        m = kpos[None, :] <= qpos[:, None]
        if window:
            m &= kpos[None, :] > qpos[:, None] - window
        return 0, _attend_block(qi, kf, vf, m[None, None], scale, cfg.logit_softcap)

    _, o = jax.lax.scan(body, 0, (jnp.arange(n_blk), qr))
    o = o.transpose(1, 0, 2, 3, 4).reshape(B, S, Hp, hd)

    hm = _head_mask(Hp, H, o.dtype)
    if hm is not None:
        o = o * hm
    return o


def self_attention(x, p, cfg, rules, positions, *, window: int = 0, cache=None):
    """Full self-attention sublayer (projections + rope + attend + out-proj).

    cache: None for train/prefill-without-cache; dict(k, v, pos) for decode.
    Returns (out, new_cache_kv or (k, v) for prefill cache building).
    """
    Hp = p["wq"].shape[1]
    H, KV = cfg.n_heads, cfg.n_kv_heads
    q, k, v = _qkv(x, p, cfg, rules, Hp)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None:
        o = causal_attention(q, k, v, cfg, rules, window=window)
        new_kv = (k, v)
    else:
        o, new_kv = _decode_attention(q, k, v, cache, cfg, window)
    hm = _head_mask(Hp, H, o.dtype)
    if hm is not None:
        o = o * hm
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    out = constrain(out, rules, ("batch", "seq", "embed"))
    return out, new_kv


def _decode_attention(q, k_new, v_new, cache, cfg, window: int):
    """Single-token decode against a (possibly ring-buffered) KV cache.

    cache: {'k': (B, Smax, KV, hd), 'v': ..., 'pos': int32 scalar}
    For windowed layers Smax == window and the buffer is a ring.
    """
    B, one, Hp, hd = q.shape
    assert one == 1
    kc, vc, pos = cache["k"], cache["v"], cache["pos"]
    Smax = kc.shape[1]
    ring = window > 0 and Smax <= window
    slot = jnp.where(ring, pos % Smax, jnp.minimum(pos, Smax - 1)) if ring else pos
    kc = jax.lax.dynamic_update_slice(kc, k_new, (0, slot.astype(jnp.int32), 0, 0))
    vc = jax.lax.dynamic_update_slice(vc, v_new, (0, slot.astype(jnp.int32), 0, 0))

    H, KV = cfg.n_heads, cfg.n_kv_heads
    kf = _expand_kv(kc, Hp, H, KV)
    vf = _expand_kv(vc, Hp, H, KV)
    scale = 1.0 / np.sqrt(hd)
    idx = jnp.arange(Smax)
    if ring:
        # every slot written so far is in-window by construction
        valid = idx < jnp.minimum(pos + 1, Smax)
    else:
        valid = idx <= pos
        if window:
            valid &= idx > pos - window
    m = valid[None, None, None, :]
    o = _attend_block(q, kf, vf, m, scale, cfg.logit_softcap)
    return o, {"k": kc, "v": vc, "pos": pos + 1}


def cross_attention(x, p, cfg, rules, media_kv):
    """Cross-attend text queries to (stub) media embeddings.

    media_kv: (B, T_media, d_model) precomputed frontend output.
    """
    Hp = p["wq"].shape[1]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    k = jnp.einsum("btd,dhk->bthk", media_kv, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", media_kv, p["wv"])
    kf = _expand_kv(k, Hp, H, KV)
    vf = _expand_kv(v, Hp, H, KV)
    o = _attend_block(q, kf, vf, None, 1.0 / np.sqrt(hd), cfg.logit_softcap)
    hm = _head_mask(Hp, H, o.dtype)
    if hm is not None:
        o = o * hm
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return constrain(out, rules, ("batch", "seq", "embed"))


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def dense(x, w):
    """``x @ w``. A weight used only here may arrive in another form that
    provides ``rmatmul(x)``: local SGD carries a large one that way, as
    the global weight plus the client's step history (``core/factored``).
    """
    rmatmul = getattr(w, "rmatmul", None)
    return x @ w if rmatmul is None else rmatmul(x)


def mlp_params(pb, cfg, name: str = "mlp"):
    d, ff = cfg.d_model, cfg.d_ff
    sub = pb.sub(name)
    if cfg.mlp == "swiglu":
        sub.param("wg", (d, ff), ("embed", "mlp"))
        sub.param("wu", (d, ff), ("embed", "mlp"))
        sub.param("wd", (ff, d), ("mlp", "embed"))
    else:
        sub.param("w1", (d, ff), ("embed", "mlp"))
        sub.param("b1", (ff,), ("mlp",), init="zeros")
        sub.param("w2", (ff, d), ("mlp", "embed"))
        sub.param("b2", (d,), ("embed",), init="zeros")


def mlp_block(x, p, cfg, rules):
    if cfg.mlp == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["wg"])
        u = jnp.einsum("bsd,df->bsf", x, p["wu"])
        g = constrain(g, rules, ("batch", "seq", "mlp"))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        out = jnp.einsum("bsf,fd->bsd", h, p["wd"])
    else:
        h = jnp.einsum("bsd,df->bsf", x, p["w1"]) + p["b1"]
        h = constrain(h, rules, ("batch", "seq", "mlp"))
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
        out = jnp.einsum("bsf,fd->bsd", h, p["w2"]) + p["b2"]
    return constrain(out, rules, ("batch", "seq", "embed"))
