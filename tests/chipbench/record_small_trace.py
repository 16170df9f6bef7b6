"""Record the small profiler trace that ``test_chipbench_trace.py`` reads.

    python3 tests/chipbench/record_small_trace.py <out.xplane.pb>

Run it on a TPU. The trace holds two rounds (``chipbench.round`` spans).
In each: a ``backend.train_s`` span around four dependent 2048x2048
matmul programs, then 20 ms of host sleep with the device idle and no
span open, then a ``backend.eval_s`` span around one small reduction,
then one more small program outside any span.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_small_trace: needs a TPU", file=sys.stderr)
        return 1
    mm = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    red = jax.jit(lambda x: jnp.sum(x[:256, :256]))
    inc = jax.jit(lambda x: x + 1.0)
    x = jnp.ones((2048, 2048), jnp.float32)
    for f in (mm, red, inc):
        jax.block_until_ready(f(x))
    with tempfile.TemporaryDirectory(dir=Path(out).parent) as tmp:
        jax.profiler.start_trace(tmp)
        for i in range(2):
            with jax.profiler.StepTraceAnnotation("chipbench.round",
                                                  step_num=i):
                with jax.profiler.TraceAnnotation("backend.train_s"):
                    y = x
                    for _ in range(4):
                        y = mm(y)
                    jax.block_until_ready(y)
                time.sleep(0.02)
                with jax.profiler.TraceAnnotation("backend.eval_s"):
                    jax.block_until_ready(red(y))
                jax.block_until_ready(inc(x))
        jax.profiler.stop_trace()
        found = sorted(Path(tmp).rglob("*.xplane.pb"))
        shutil.copy(found[0], out)
    print(f"wrote {out}: {Path(out).stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
