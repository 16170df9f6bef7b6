"""Record the small four-chip profiler trace that
``test_chipbench_olmo.py`` reads.

    python3 tests/chipbench/record_gradient_trace.py <out.xplane.pb>

Run it on a host with four TPU chips. It drives the program's gradient
regime as the OLMo cell does, at a small size: ``RoundLoop`` over
``GradientBackend`` with OLMo at 2 layers of width 512 (vocab 2048) on a
(4, 1) ``("data", "model")`` mesh with the FSDP rules, 8 rows of 256
tokens, ``sfl_two_step`` over 2 ONUs x 4 clients. One round compiles
outside the trace; the trace holds the next two, each in a
``chipbench.round`` step span: the program's ``round.transport``,
``round.minibatch`` and ``backend.train_step_s`` spans, and on each
device the step's ops, its FSDP all-gathers, reduce-scatters and
all-reduces among them. The Python tracer is off, so the file stays small.
"""
from __future__ import annotations

import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(out: str) -> int:
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import numpy as np

    from repro import configs, fl
    from repro.core.fedavg import FLConfig
    from repro.launch.mesh import make_test_mesh
    from repro.launch.train import build_rules

    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < 4:
        print("record_gradient_trace: needs four TPU chips", file=sys.stderr)
        return 1
    cfg = configs.get_smoke("olmo_1b", n_kv_heads=4, d_model=512,
                            head_dim=128, d_ff=2048, vocab_size=2048,
                            q_chunk=128)
    flc = FLConfig(n_onus=2, clients_per_onu=4, n_selected=8, local_steps=1)
    exp = fl.ExperimentConfig(fl=flc, strategy="sfl_two_step", seed=7)
    strategy = exp.make_strategy()
    mesh = make_test_mesh((4, 1), ("data", "model"))
    backend = fl.GradientBackend(
        cfg, strategy, mesh, build_rules(mesh, strategy.transport),
        lr=4e-4, batch=8, seq=256, seed=7,
        sample_counts=np.arange(50, 58, dtype=np.float32),
        onu_ids=np.arange(8) // 4)
    loop = fl.RoundLoop(exp, backend)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.set_mesh(mesh):
        loop.run_round(0)
        with tempfile.TemporaryDirectory(dir=Path(out).parent) as tmp:
            jax.profiler.start_trace(tmp, profiler_options=options)
            for rnd in (1, 2):
                with jax.profiler.StepTraceAnnotation("chipbench.round",
                                                      step_num=rnd):
                    loop.run_round(rnd)
                    jax.block_until_ready(backend.params)
            jax.profiler.stop_trace()
            (found,) = Path(tmp).rglob("*.xplane.pb")
            shutil.copyfile(found, out)
    print(f"record_gradient_trace: wrote {out} "
          f"({Path(out).stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
