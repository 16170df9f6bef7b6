"""Jit'd public wrappers for the Pallas kernels with backend dispatch.

On TPU the Pallas kernels run compiled (interpret=False); on a CPU host
they execute in interpret mode when explicitly requested (tests) and
otherwise fall back to the jnp reference — which is also what a GSPMD
lowering on the CPU sees, since Mosaic kernels cannot lower for the CPU
backend.
:func:`dispatch_mode` is the single choke point, and what a benchmark row
stamps so that a reference timing is never read as a kernel time.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import ref
from repro.kernels.agg_reduce import agg_reduce as _agg_pallas
from repro.kernels.agg_reduce import agg_reduce_quant as _agg_quant_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.quantize import dequantize_int8 as _dequant_pallas
from repro.kernels.quantize import quantize_int4 as _quant4_pallas
from repro.kernels.quantize import quantize_int8 as _quant_pallas
from repro.kernels.quantize import topk_sparsify as _topk_pallas
from repro.kernels.rglru_scan import rglru_scan as _rglru_pallas
from repro.kernels.rwkv6_scan import rwkv6_scan as _rwkv_pallas
from repro.obs.profile import named_scope


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def dispatch_mode(use_pallas: Optional[bool] = None) -> str:
    """'compiled' | 'interpret' | 'ref' — what a call with ``use_pallas``
    runs on the default backend."""
    if use_pallas is None:
        return "compiled" if _on_tpu() else "ref"
    if use_pallas:
        return "compiled" if _on_tpu() else "interpret"
    return "ref"


# jax.named_scope names the HLO emitted under each kernel, so device
# profiles (and jax.profiler captures) show agg_reduce/quantize/... as
# named regions regardless of dispatch mode — the in-jit counterpart of
# repro.obs.profile.span

@functools.partial(jax.jit, static_argnames=("use_pallas",))
def agg_reduce(x, weights, mask, use_pallas: Optional[bool] = None):
    with named_scope("kernels.agg_reduce"):
        m = dispatch_mode(use_pallas)
        if m == "ref":
            return ref.agg_reduce_ref(x, weights, mask)
        return _agg_pallas(x, weights, mask, interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def quantize_int8(x, key, use_pallas: Optional[bool] = None):
    with named_scope("kernels.quantize_int8"):
        m = dispatch_mode(use_pallas)
        if m == "ref":
            return ref.quantize_int8_ref(x, key)
        return _quant_pallas(x, key, interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def dequantize_int8(q, scale, use_pallas: Optional[bool] = None):
    with named_scope("kernels.dequantize_int8"):
        m = dispatch_mode(use_pallas)
        if m == "ref":
            return ref.dequantize_int8_ref(q, scale)
        return _dequant_pallas(q, scale, interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def quantize_int4(x, key, use_pallas: Optional[bool] = None):
    with named_scope("kernels.quantize_int4"):
        m = dispatch_mode(use_pallas)
        if m == "ref":
            return ref.quantize_int4_ref(x, key)
        return _quant4_pallas(x, key, interpret=(m == "interpret"))


# int4 shares the int8 dequant math (int8-typed values × f32 scale);
# only the wire format differs, which compressed_bytes accounts for
dequantize_int4 = dequantize_int8


@functools.partial(jax.jit, static_argnames=("k", "use_pallas"))
def topk_sparsify(x, k: int, use_pallas: Optional[bool] = None):
    with named_scope("kernels.topk_sparsify"):
        m = dispatch_mode(use_pallas)
        if m == "ref":
            return ref.topk_sparsify_ref(x, k)
        return _topk_pallas(x, k, interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("bits", "use_pallas"))
def agg_reduce_quant(x, weights, mask, key, bits: int = 8,
                     use_pallas: Optional[bool] = None):
    with named_scope("kernels.agg_reduce_quant"):
        m = dispatch_mode(use_pallas)
        if m == "ref":
            return ref.agg_reduce_quant_ref(x, weights, mask, key, bits)
        return _agg_quant_pallas(x, weights, mask, key, bits=bits,
                                 interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("causal", "window", "use_pallas"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    use_pallas: Optional[bool] = None):
    with named_scope("kernels.flash_attention"):
        m = dispatch_mode(use_pallas)
        if m == "ref":
            return ref.attention_ref(q, k, v, causal=causal, window=window)
        return _flash_pallas(q, k, v, causal=causal, window=window,
                             interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def rglru_scan(a, b, h0=None, use_pallas: Optional[bool] = None):
    with named_scope("kernels.rglru_scan"):
        m = dispatch_mode(use_pallas)
        if m == "ref":
            return ref.rglru_scan_ref(a, b, h0)
        return _rglru_pallas(a, b, h0, interpret=(m == "interpret"))


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def rwkv6_scan(r, k, v, logw, u, use_pallas: Optional[bool] = None):
    with named_scope("kernels.rwkv6_scan"):
        m = dispatch_mode(use_pallas)
        if m == "ref":
            return ref.rwkv6_ref(r, k, v, logw, u)
        return _rwkv_pallas(r, k, v, logw, u, interpret=(m == "interpret"))
