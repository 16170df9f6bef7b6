"""Pallas TPU kernel: the ONU aggregation function (AF) — masked weighted
reduction over a stacked client axis, plus the fused aggregate+quantize
form used by the compressed θ→Φ→Ψ transport.

    out[n] = Σ_c  weight[c] · mask[c] · x[c, n]

This is the paper's per-ONU hot loop (θ_i = Σ_j k_ij w_ij) in the
client-stacked FL regime: x is a (clients, flat_params) tile of local model
deltas. The kernel tiles the parameter axis into VMEM-resident blocks
aligned to the VPU lane width (multiples of 128) and keeps the full client
axis resident (C is small: ≤ clients-per-ONU), accumulating in f32.

``agg_reduce_quant`` fuses the compression PR's int8/int4 quantization into
the same pass: the per-block absmax needed for the quantization scale is
computed while the aggregate is still VMEM-resident (pass A emits the
aggregate and a running max|θ| together), so the θ tile is never re-read
from HBM just to find its dynamic range; pass B is the standard
stochastic-rounding quantize (kernels/quantize.py) at the max|θ|/qmax
scale.

Zero-length inputs (C=0 when every client of an ONU crashed, N=0 for an
empty parameter group) return exact zeros / identity scale early — an empty
pallas_call grid is an error, and the math is trivially Σ over nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.quantize import SCALAR_SPEC, _make_quant_kernel, _qmax

BLOCK_N = 2048  # f32 VMEM tile: C×2048×4B ≤ ~0.5 MB for C ≤ 64
_AMAX_TILE = (8, 128)  # the running max|θ|, one f32 VMEM tile


def _agg_kernel(x_ref, w_ref, out_ref):
    # x_ref: (C, BLOCK_N) in VMEM; w_ref: (C, 1); out: (BLOCK_N,)
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)           # (C, 1) — weight·mask folded
    out_ref[...] = jnp.sum(x * w, axis=0)


def _agg_absmax_kernel(x_ref, w_ref, out_ref, amax_ref):
    # same reduction, but also fold this block's max|Σ| into a running max
    # while it is still in VMEM — the fusion that saves the extra HBM pass
    # before quantizing. amax_ref is one lane-aligned (8, 128) tile mapped
    # to the same block at every grid step, so it stays resident and is
    # written back once (the grid runs sequentially).
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    s = jnp.sum(x * w, axis=0)
    out_ref[...] = s

    @pl.when(pl.program_id(0) == 0)
    def _():
        amax_ref[...] = jnp.zeros(amax_ref.shape, jnp.float32)

    amax_ref[...] = jnp.maximum(amax_ref[...], jnp.max(jnp.abs(s)))


def _padded(x, N: int, block_n: int):
    bn = min(block_n, max(128, 128 * ((N + 127) // 128)))
    pad = (-N) % bn
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x, N + pad, bn


def agg_reduce(x, weights, mask, *, block_n: int = BLOCK_N, interpret: bool = False):
    """x: (C, N) f32/bf16; weights, mask: (C,) -> (N,) f32.

    N is padded to a block multiple internally.
    """
    C, N = x.shape
    if C == 0 or N == 0:
        return jnp.zeros((N,), jnp.float32)
    w = (weights.astype(jnp.float32) * mask.astype(jnp.float32)).reshape(C, 1)
    x, npad, bn = _padded(x, N, block_n)
    grid = (npad // bn,)
    out = pl.pallas_call(
        _agg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((C, bn), lambda i: (0, i)),
            pl.BlockSpec((C, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((npad,), jnp.float32),
        interpret=interpret,
    )(x, w)
    return out[:N]


def agg_reduce_quant(x, weights, mask, key, *, bits: int = 8,
                     block_n: int = BLOCK_N, interpret: bool = False):
    """Fused masked-weighted reduce + stochastic-rounding quantize.

    x: (C, N), weights/mask: (C,) -> (q int8 (N,), scale f32 scalar) such
    that dequantize(q, scale) ≈ agg_reduce(x, weights, mask) within one
    quantization step. This is the ONU's compressed-uplink hot path: θ is
    aggregated and its dynamic range measured in one VMEM pass, then
    quantized at max|θ|/qmax before the PON upstream.
    """
    C, N = x.shape
    qmax = _qmax(bits)
    if C == 0 or N == 0:
        return jnp.zeros((N,), jnp.int8), jnp.float32(1.0)
    w = (weights.astype(jnp.float32) * mask.astype(jnp.float32)).reshape(C, 1)
    x, npad, bn = _padded(x, N, block_n)
    grid = (npad // bn,)
    agg, amax = pl.pallas_call(
        _agg_absmax_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((C, bn), lambda i: (0, i)),
            pl.BlockSpec((C, 1), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn,), lambda i: (i,)),
            pl.BlockSpec(_AMAX_TILE, lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((npad,), jnp.float32),
            jax.ShapeDtypeStruct(_AMAX_TILE, jnp.float32),
        ],
        interpret=interpret,
    )(x, w)
    scale = jnp.maximum(amax[0, 0], 1e-12) / qmax
    # pass B: the standard quantize kernel over the padded aggregate
    # (padding quantizes to 0 and is sliced off)
    noise = jax.random.uniform(key, (N,), jnp.float32)
    if npad != N:
        noise = jnp.pad(noise, (0, npad - N))
    q = pl.pallas_call(
        _make_quant_kernel(qmax),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn,), lambda i: (i,)),
            pl.BlockSpec((bn,), lambda i: (i,)),
            SCALAR_SPEC,
        ],
        out_specs=pl.BlockSpec((bn,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((npad,), jnp.int8),
        interpret=interpret,
    )(agg, noise, scale.reshape(1))
    return q[:N], scale
