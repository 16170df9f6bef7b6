"""Pallas TPU kernels: int8/int4 stochastic-rounding quantize / dequantize
and the top-k threshold mask.

Used on the constrained uplink (cross-pod hop / client→ONU leg) to shrink
bf16/f32 traffic 4–8x (beyond-paper optimization; see core/compression.py
for the jnp form, wire accounting, and the error-feedback state).

The uniform noise is generated outside the kernel (jax.random) and streamed
in — keeps the kernel portable across Mosaic versions and bit-exact with
the jnp reference. The top-k threshold is likewise computed outside
(jax.lax.top_k has a tuned TPU lowering); the kernel applies the magnitude
mask in one VMEM pass. Tiles are (8k,) f32 VMEM blocks. int4 values are
carried unpacked (int8 in [-7, 7]) — the 2-elements/byte nibble packing
(``pack_int4``/``unpack_int4``) matters for the wire accounting, not the
on-device layout, which stays lane-aligned.

All entry points guard zero-length inputs (N=0 is reachable when every
client of an ONU crashes mid-round) with early returns — ``jnp.max`` over
an empty axis is an error, and a zero-element pallas_call is pointless.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 8192

# A scalar operand (scale, threshold) lives in SMEM: the chip's compiler
# only loads from VMEM/SMEM refs, never from an unplaced (ANY) one.
SCALAR_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _qmax(bits: int) -> float:
    if bits not in (4, 8):
        raise ValueError(f"unsupported quantization width: {bits} bits")
    return float(2 ** (bits - 1) - 1)


def _make_quant_kernel(qmax: float):
    def _quant_kernel(x_ref, noise_ref, scale_ref, q_ref):
        x = x_ref[...].astype(jnp.float32)
        s = scale_ref[0]
        y = x / s + (noise_ref[...] - 0.5)
        q_ref[...] = jnp.clip(jnp.round(y), -qmax, qmax).astype(jnp.int8)
    return _quant_kernel


def _dequant_kernel(q_ref, scale_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * scale_ref[0]


def _block_shape(N: int, block: int) -> int:
    return min(block, max(128, 128 * ((N + 127) // 128)))


def quantize_intb(x, key, bits: int, *, block: int = BLOCK,
                  interpret: bool = False):
    """x: (N,) -> (q int8 (N,), scale f32 scalar). Unbiased (stochastic).

    ``bits`` picks the symmetric range: int8 → [-127, 127], int4 →
    [-7, 7] (unpacked; see ``pack_int4`` for the wire layout)."""
    (N,) = x.shape
    qmax = _qmax(bits)
    if N == 0:
        return jnp.zeros((0,), jnp.int8), jnp.float32(1.0)
    scale = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-12) / qmax
    noise = jax.random.uniform(key, (N,), jnp.float32)
    bn = _block_shape(N, block)
    pad = (-N) % bn
    if pad:
        x = jnp.pad(x, (0, pad))
        noise = jnp.pad(noise, (0, pad))
    npad = N + pad
    q = pl.pallas_call(
        _make_quant_kernel(qmax),
        grid=(npad // bn,),
        in_specs=[
            pl.BlockSpec((bn,), lambda i: (i,)),
            pl.BlockSpec((bn,), lambda i: (i,)),
            SCALAR_SPEC,
        ],
        out_specs=pl.BlockSpec((bn,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((npad,), jnp.int8),
        interpret=interpret,
    )(x, noise, scale.reshape(1))
    return q[:N], scale


quantize_int8 = functools.partial(quantize_intb, bits=8)
quantize_int4 = functools.partial(quantize_intb, bits=4)


def dequantize_int8(q, scale, *, block: int = BLOCK, interpret: bool = False):
    (N,) = q.shape
    if N == 0:
        return jnp.zeros((0,), jnp.float32)
    bn = _block_shape(N, block)
    pad = (-N) % bn
    if pad:
        q = jnp.pad(q, (0, pad))
    npad = N + pad
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(npad // bn,),
        in_specs=[
            pl.BlockSpec((bn,), lambda i: (i,)),
            SCALAR_SPEC,
        ],
        out_specs=pl.BlockSpec((bn,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((npad,), jnp.float32),
        interpret=interpret,
    )(q, scale.reshape(1))
    return x[:N]


# int4 carries the same (int8-typed values, f32 scale) pair on device;
# only the wire format differs, which compressed_bytes accounts for.
dequantize_int4 = dequantize_int8


# ---------------------------------------------------------------------------
# top-k magnitude sparsification
# ---------------------------------------------------------------------------

def _topk_kernel(x_ref, thresh_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)
    keep = jnp.abs(x) >= thresh_ref[0]
    out_ref[...] = jnp.where(keep, x, 0.0)


def topk_mask(x, thresh, *, block: int = BLOCK, interpret: bool = False):
    """x: (N,) -> (N,) f32 with |x| < thresh zeroed (dense output).

    The threshold (the k-th largest |x|) comes from the caller — see
    ``topk_sparsify`` — so the kernel is one branch-free VMEM pass.
    """
    (N,) = x.shape
    if N == 0:
        return jnp.zeros((0,), jnp.float32)
    bn = _block_shape(N, block)
    pad = (-N) % bn
    if pad:
        x = jnp.pad(x, (0, pad))
    npad = N + pad
    out = pl.pallas_call(
        _topk_kernel,
        grid=(npad // bn,),
        in_specs=[
            pl.BlockSpec((bn,), lambda i: (i,)),
            SCALAR_SPEC,
        ],
        out_specs=pl.BlockSpec((bn,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((npad,), jnp.float32),
        interpret=interpret,
    )(x, jnp.asarray(thresh, jnp.float32).reshape(1))
    return out[:N]


def topk_threshold(x, k: int):
    """The k-th largest |x| — ties at the threshold are all kept (the wire
    accounting bills exactly k; DESIGN.md §17)."""
    k = max(1, min(int(k), x.shape[0]))
    return jax.lax.top_k(jnp.abs(x.astype(jnp.float32)), k)[0][-1]


def topk_sparsify(x, k: int, *, block: int = BLOCK, interpret: bool = False):
    """x: (N,) -> dense (N,) f32 keeping the k largest-magnitude entries."""
    (N,) = x.shape
    if N == 0:
        return jnp.zeros((0,), jnp.float32)
    return topk_mask(x, topk_threshold(x, k), block=block, interpret=interpret)


# ---------------------------------------------------------------------------
# int4 nibble packing (wire layout; jnp — packing is not a hot path, the
# payload crosses the PCIe/NIC boundary exactly once per round)
# ---------------------------------------------------------------------------

def pack_int4(q):
    """q int8 (N,) in [-7, 7] -> uint8 (ceil(N/2),), two nibbles per byte
    (low nibble = even index). Odd N pads the final high nibble with 0."""
    (N,) = q.shape
    if N == 0:
        return jnp.zeros((0,), jnp.uint8)
    if N % 2:
        q = jnp.pad(q, (0, 1))
    u = (q.astype(jnp.int32) & 0xF).astype(jnp.uint8)
    return (u[0::2] | (u[1::2] << 4)).astype(jnp.uint8)


def unpack_int4(packed, n: int):
    """uint8 (ceil(n/2),) -> int8 (n,) sign-extended from each nibble."""
    if n == 0:
        return jnp.zeros((0,), jnp.int8)
    lo = (packed & 0xF).astype(jnp.int8)
    hi = (packed >> 4).astype(jnp.int8)
    both = jnp.stack([lo, hi], axis=1).reshape(-1)[:n]
    # sign-extend the 4-bit two's complement
    return jnp.where(both >= 8, both - 16, both).astype(jnp.int8)
