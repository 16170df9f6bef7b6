"""Kernel micro-bench: us_per_call for the ONU aggregation + quantize ops,
plus derived wire-bytes — one row per transport variant.

Every row stamps the dispatch mode that ran (``compiled`` Pallas on a TPU,
the jnp ``ref`` path elsewhere; see ``kernels.ops.dispatch_mode``) and the
device, so a CPU reference timing can never be read as a kernel time.

Per-rep wall times are recorded into the ambient ``repro.obs`` metrics
registry (histograms ``kernels.<name>.us``) so a ``--metrics-out`` session
wrapping the bench captures the full distribution, not just the mean.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.obs.context import get as _obs_get


def _time(name, fn, *args, reps=5):
    """Mean µs/call over ``reps`` post-compile reps; each rep's wall time
    also lands in the ambient obs histogram ``kernels.<name>.us``."""
    hist = _obs_get().metrics.histogram(f"kernels.{name}.us")
    fn(*args)  # compile
    jax.block_until_ready(fn(*args))
    per_rep = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        us = (time.perf_counter() - t0) * 1e6
        per_rep.append(us)
        hist.observe(us)
    return float(np.mean(per_rep))


def main():
    from benchmarks import report

    # independent streams for data, weights, and quantizer noise — one
    # key is consumed at most once (REPRO203)
    kx, kw, kq, kd, kq4, kf = jax.random.split(jax.random.PRNGKey(0), 6)
    # the paper's ONU AF over one ONU's clients (20 x 6.6M-param CNN)
    C, N = 20, 6_603_710
    x = jax.random.normal(kx, (C, N), jnp.float32)
    w = jax.random.uniform(kw, (C,)) * 100
    m = jnp.ones((C,))
    rows = []
    us = _time("agg_reduce", lambda a, b, c: ops.agg_reduce(a, b, c), x, w, m)
    rows.append({"name": "agg_reduce_onu20x6.6M", "us_per_call": us,
                 "derived": f"gbps={C*N*4/us/1e3:.1f}"})
    q_us = _time("quantize_int8", lambda a: ops.quantize_int8(a, kq), x[0])
    rows.append({"name": "quantize_int8_6.6M", "us_per_call": q_us,
                 "derived": "wire_reduction=4x"})
    qq, ss = ops.quantize_int8(x[0], kd)
    d_us = _time("dequantize_int8",
                 lambda a, s: ops.dequantize_int8(a, s), qq, ss)
    rows.append({"name": "dequantize_int8_6.6M", "us_per_call": d_us,
                 "derived": ""})
    q4_us = _time("quantize_int4", lambda a: ops.quantize_int4(a, kq4), x[0])
    rows.append({"name": "quantize_int4_6.6M", "us_per_call": q4_us,
                 "derived": "wire_reduction=8x"})
    k = max(1, N // 100)
    t_us = _time("topk_sparsify", lambda a: ops.topk_sparsify(a, k), x[0])
    rows.append({"name": "topk_sparsify_1pct_6.6M", "us_per_call": t_us,
                 "derived": f"k={k}"})
    f_us = _time("agg_reduce_quant",
                 lambda a, b, c: ops.agg_reduce_quant(a, b, c, kf), x, w, m)
    rows.append({"name": "agg_reduce_quant_onu20x6.6M", "us_per_call": f_us,
                 "derived": "fused_agg+int8"})
    dev = jax.devices()[0]
    for r in rows:
        r["dispatch"] = ops.dispatch_mode()
        r["device"] = f"{dev.platform}:{dev.device_kind}"
    return report.emit_rows(
        rows, "kernels",
        [("name", ""), ("us_per_call", ".0f"), ("derived", ""),
         ("dispatch", ""), ("device", "")],
        header="bench_kernels")


if __name__ == "__main__":
    main()
