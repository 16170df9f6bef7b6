"""End-to-end behaviour tests for the paper's system.

These exercise the public entry points the way the examples do: real train
steps on the CPU device, serve prefill+decode, and the multi-pod train step
on a small fake mesh (subprocess: device-count flags must precede jax init).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")


def _run(script: str) -> str:
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                       capture_output=True, text=True,
                       env={"PYTHONPATH": "src", "PATH": os.environ.get("PATH", ""),
                            "JAX_PLATFORMS": "cpu"},
                       cwd=REPO, timeout=560)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    return r.stdout


def test_train_loss_decreases():
    """A reduced LM learns the synthetic Markov stream (loss drops)."""
    from repro import configs
    from repro.common.sharding import ShardingRules
    from repro.data import lm
    from repro.launch.specs import make_train_step
    from repro.models import transformer
    from repro.optim import make_optimizer

    cfg = configs.get_smoke("olmo_1b")
    rules = ShardingRules(batch=None, fsdp=None, tensor=None, expert=None)
    params, _ = transformer.init_params(cfg, jax.random.PRNGKey(0))
    opt = make_optimizer("adamw")
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, rules, "adamw", 3e-3))
    losses = []
    gen = lm.lm_batches(0, 30, 8, 64, cfg.vocab_size)
    for i, b in enumerate(gen):
        batch = {"tokens": jnp.asarray(b["tokens"]),
                 "client_weight": jnp.ones((8,), jnp.float32)}
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.2, losses[:3] + losses[-3:]


def test_microbatched_step_matches_plain():
    """grad accumulation (n_micro=4) == single-shot step, same update."""
    from repro import configs
    from repro.common.sharding import ShardingRules
    from repro.launch.specs import make_train_step
    from repro.models import transformer
    from repro.optim import make_optimizer

    cfg = configs.get_smoke("qwen2_0_5b")
    rules = ShardingRules(batch=None, fsdp=None, tensor=None, expert=None)
    params, _ = transformer.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                          cfg.vocab_size),
             "client_weight": jnp.ones((8,), jnp.float32)}
    p1, _, l1 = jax.jit(make_train_step(cfg, rules, "sgd", 0.1, 1))(params, {}, batch)
    p4, _, l4 = jax.jit(make_train_step(cfg, rules, "sgd", 0.1, 4))(params, {}, batch)
    # microbatch losses average to ~the same value; updates near-identical
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=2e-2, atol=2e-3)


def test_serve_driver_runs():
    out = _run("""
        import sys
        sys.argv = ["serve", "--arch", "rwkv6-3b", "--smoke", "--batch", "2",
                    "--prompt-len", "32", "--gen", "4"]
        from repro.launch import serve
        serve.main()
    """)
    assert "decode 4 steps" in out


def test_train_driver_with_pon_and_checkpoint(tmp_path):
    # the compile cache goes under tmp_path, not into the checkout
    cache = f"import os; os.environ['JAX_COMPILATION_CACHE_DIR'] = r'{tmp_path / 'jax_cache'}'"
    out = _run(f"""
        {cache}
        import sys
        sys.argv = ["train", "--arch", "qwen2-0.5b", "--smoke", "--steps", "3",
                    "--batch", "4", "--seq", "32", "--ckpt", r"{tmp_path}",
                    "--ckpt-every", "100"]
        from repro.launch import train
        train.main()
    """)
    assert "saved final" in out
    out2 = _run(f"""
        {cache}
        import sys
        sys.argv = ["train", "--arch", "qwen2-0.5b", "--smoke", "--steps", "5",
                    "--batch", "4", "--seq", "32", "--ckpt", r"{tmp_path}"]
        from repro.launch import train
        train.main()
    """)
    assert "resumed from step 3" in out2


def test_multipod_train_step_compiles_subprocess():
    """lower+compile a smoke config's train step on a fake 2x2x2 multi-pod
    mesh through ``specs.input_specs`` with the FSDP shardings."""
    out = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        from repro import configs
        from repro.common.sharding import ShardingRules
        from repro.launch import specs as S
        from repro.launch.mesh import make_test_mesh
        from repro.models.config import ShapeConfig

        cfg = configs.get_smoke("recurrentgemma_9b")
        shp = ShapeConfig("t", 64, 8, "train")
        mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
        rules = ShardingRules(batch=("pod", "data"), fsdp="data",
                              tensor="model", expert="model")
        with jax.set_mesh(mesh):
            fn, args, _ = S.input_specs(cfg, shp, mesh, rules, "adamw")
            compiled = jax.jit(fn).lower(*args).compile()
            print("mem", compiled.memory_analysis().temp_size_in_bytes)
        print("MULTIPOD_OK")
    """)
    assert "MULTIPOD_OK" in out


def test_sfl_vs_classical_cross_pod_traffic():
    """THE paper claim, on collectives: the SFL (FSDP two-step) schedule
    moves fewer cross-pod bytes than the classical flat all-reduce, read
    from the collectives of each compiled train step."""
    out = _run("""
        import os
        import sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        sys.path.insert(0, "tests")
        import jax
        from collective_bytes import axis_bytes
        from repro import configs
        from repro.common.sharding import ShardingRules
        from repro.launch import specs as S
        from repro.launch.mesh import make_test_mesh
        from repro.models.config import ShapeConfig

        cfg = configs.get_smoke("olmo_1b")
        shp = ShapeConfig("t", 64, 8, "train")
        mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
        sfl = ShardingRules(batch=("pod", "data"), fsdp="data",
                            tensor="model", expert="model")
        cls = sfl.replicated()
        pod = {}
        for name, rules in (("sfl", sfl), ("classical", cls)):
            fn, args, _ = S.input_specs(cfg, shp, mesh, rules, "sgd")
            with jax.set_mesh(mesh):
                txt = jax.jit(fn).lower(*args).compile().as_text()
            pod[name] = axis_bytes(txt, mesh)["pod"]
        print("POD", pod["sfl"], pod["classical"])
        assert pod["sfl"] < pod["classical"], pod
        print("SFL_TRAFFIC_OK")
    """)
    assert "SFL_TRAFFIC_OK" in out


@pytest.mark.parametrize("env_dir", [None, "from_env"])
def test_compile_cache_location(tmp_path, env_dir):
    """Unset JAX_COMPILATION_CACHE_DIR: the cache goes to the fixed in-tree
    ``.jax_cache``; set: JAX reads the variable and nothing is set in code."""
    env = {"PYTHONPATH": "src", "PATH": os.environ.get("PATH", ""),
           "JAX_PLATFORMS": "cpu"}
    want = os.path.realpath(os.path.join(REPO, ".jax_cache"))
    if env_dir is not None:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent("""
            import jax
            from repro.common.compile_cache import enable_compile_cache
            print(enable_compile_cache())
            print(jax.config.jax_compilation_cache_dir)
        """)], capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [want, want]
