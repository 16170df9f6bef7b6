"""Record the small profiler trace that ``test_chipbench_spans.py`` reads.

    python3 tests/chipbench/record_span_trace.py <out.xplane.pb>

Run it on a TPU. The trace holds two rounds (``chipbench.round`` spans),
each opened with the program's own span helper (``repro.obs.profile``):

- ``round.transport`` (``n_selected=8``, ``involved=6``): 10 ms of host
  sleep, then 10 ms more with no span open (the driver);
- ``round.minibatch`` (``n_clients=16``, ``h2d_bytes``): one 2 MiB host
  array put on the device;
- ``backend.train_s`` around two ``train.chunk`` spans (``chunk``,
  ``rows=16``): the first calls a jit made afresh each round, so JAX
  traces, lowers and compiles it inside the span with the device idle;
  each dispatches four dependent 2048x2048 matmul programs;
- ``round.aggregate`` around one small program;
- ``backend.eval_s`` around one small reduction.

The Python tracer is off: the program's spans and JAX's compile markers
are all the reduction needs, and the file stays small.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(out: str) -> int:
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.obs import profile

    if jax.devices()[0].platform != "tpu":
        print("record_span_trace: needs a TPU", file=sys.stderr)
        return 1
    mm = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    red = jax.jit(lambda x: jnp.sum(x[:256, :256]))
    inc = jax.jit(lambda x: x + 1.0)
    x = jnp.ones((2048, 2048), jnp.float32)
    host = np.ones((512, 1024), np.float32)
    for f in (mm, red, inc):
        jax.block_until_ready(f(x))
    jax.block_until_ready(inc(host))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory(dir=Path(out).parent) as tmp:
        jax.profiler.start_trace(tmp, profiler_options=options)
        for i in range(2):
            with jax.profiler.StepTraceAnnotation("chipbench.round",
                                                  step_num=i):
                with profile.span("round.transport", n_selected=8,
                                  involved=6):
                    time.sleep(0.01)
                time.sleep(0.01)
                with profile.span("round.minibatch", n_clients=16,
                                  h2d_bytes=host.nbytes):
                    b = jax.block_until_ready(jax.device_put(host))
                with profile.span("backend.train_s"):
                    y = x
                    for c in range(2):
                        with profile.span("train.chunk", chunk=c, rows=16):
                            if c == 0:
                                fresh = jax.jit(lambda v: v * 2.0 + 1.0)
                                y = fresh(y)
                            for _ in range(4):
                                y = mm(y)
                    jax.block_until_ready(y)
                with profile.span("round.aggregate"):
                    jax.block_until_ready(inc(b))
                with profile.span("backend.eval_s"):
                    jax.block_until_ready(red(y))
        jax.profiler.stop_trace()
        found = sorted(Path(tmp).rglob("*.xplane.pb"))
        shutil.copy(found[0], out)
    print(f"wrote {out}: {Path(out).stat().st_size} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
