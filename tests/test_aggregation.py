"""The paper's aggregation invariants (unit + property + multi-device)."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from hypothesis_compat import given, settings, st  # optional dev dep
from repro.core import aggregation as agg


def _rand_tree(rng, C):
    return {
        "w": jnp.asarray(rng.normal(size=(C, 4, 3)).astype(np.float32)),
        "b": jnp.asarray(rng.normal(size=(C, 7)).astype(np.float32)),
    }


@settings(max_examples=25, deadline=None)
@given(C=st.integers(1, 40), n_onus=st.integers(1, 8), seed=st.integers(0, 2**30))
def test_two_step_equals_classical_equals_oracle(C, n_onus, seed):
    """Σ_i θ_i / K == Σ_ij k w / K — the paper's central identity."""
    rng = np.random.default_rng(seed)
    tree = _rand_tree(rng, C)
    weights = jnp.asarray(rng.uniform(1, 100, C).astype(np.float32))
    mask = jnp.asarray((rng.random(C) > 0.3).astype(np.float32))
    onu = jnp.asarray(rng.integers(0, n_onus, C))
    two, thetas, K1 = agg.segment_aggregate(tree, weights, mask, onu, n_onus)
    cls, K2 = agg.classical_aggregate(tree, weights, mask)
    assert np.isclose(float(K1), float(K2))
    for k in tree:
        np.testing.assert_allclose(np.asarray(two[k]), np.asarray(cls[k]),
                                   rtol=1e-5, atol=1e-5)
        want, _ = agg.numpy_weighted_mean(np.asarray(tree[k]),
                                          np.asarray(weights), np.asarray(mask))
        np.testing.assert_allclose(np.asarray(two[k]), want, rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**30))
def test_onu_grouping_invariance(seed):
    """The aggregate is invariant to which ONU each client hangs off."""
    rng = np.random.default_rng(seed)
    C = 24
    tree = _rand_tree(rng, C)
    weights = jnp.asarray(rng.uniform(1, 50, C).astype(np.float32))
    mask = jnp.ones((C,), jnp.float32)
    a1, _, _ = agg.segment_aggregate(
        tree, weights, mask, jnp.asarray(rng.integers(0, 4, C)), 4)
    a2, _, _ = agg.segment_aggregate(
        tree, weights, mask, jnp.asarray(rng.integers(0, 16, C)), 16)
    for k in tree:
        np.testing.assert_allclose(np.asarray(a1[k]), np.asarray(a2[k]),
                                   rtol=1e-5, atol=1e-5)


def test_mask_renormalization():
    """Dropping a straggler renormalizes by the surviving K (unbiased)."""
    rng = np.random.default_rng(0)
    tree = {"w": jnp.asarray(rng.normal(size=(3, 5)).astype(np.float32))}
    weights = jnp.asarray([10.0, 20.0, 30.0])
    mask = jnp.asarray([1.0, 1.0, 0.0])
    out, _, K = agg.segment_aggregate(tree, weights, mask, jnp.asarray([0, 1, 1]), 2)
    want = (10 * np.asarray(tree["w"][0]) + 20 * np.asarray(tree["w"][1])) / 30.0
    assert float(K) == 30.0
    np.testing.assert_allclose(np.asarray(out["w"]), want, rtol=1e-6)


def test_all_masked_is_safe():
    tree = {"w": jnp.ones((4, 3))}
    out, _, K = agg.segment_aggregate(tree, jnp.ones(4), jnp.zeros(4),
                                      jnp.zeros(4, jnp.int32), 2)
    assert float(K) == 0.0
    assert np.all(np.isfinite(np.asarray(out["w"])))


_SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core import aggregation as agg
    from repro.launch.mesh import make_test_mesh

    mesh = make_test_mesh((2, 4), ("pod", "data"))
    C = 8
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(C, 6, 5)).astype(np.float32))
    w = jnp.asarray(rng.uniform(1, 10, C).astype(np.float32))

    def worker(xs, ws, mode):
        local = jax.tree.map(lambda t: t[0] * ws[0], {"g": xs})
        f = agg.make_weighted_gradient_aggregator(mesh, mode)
        mean, K = f(local, ws[0])
        return mean["g"], K

    outs = {}
    for mode in ("two_step", "classical"):
        fn = shard_map(lambda xs, ws: worker(xs, ws, mode), mesh=mesh,
                       in_specs=(P(("pod", "data")), P(("pod", "data"))),
                       out_specs=(P(), P()), check_vma=False)
        m, K = jax.jit(fn)(x, w)
        outs[mode] = (np.asarray(m), float(K))
    want, Kw = agg.numpy_weighted_mean(np.asarray(x), np.asarray(w), np.ones(C))
    for mode, (m, K) in outs.items():
        assert np.isclose(K, Kw), (mode, K, Kw)
        np.testing.assert_allclose(m, want, rtol=1e-5, atol=1e-5)
    # int8-compressed cross-pod hop: unbiased, so close but not exact
    fn = shard_map(lambda xs, ws: worker(xs, ws, "two_step"), mesh=mesh,
                   in_specs=(P(("pod", "data")), P(("pod", "data"))),
                   out_specs=(P(), P()), check_vma=False)
    print("SPMD_AGG_OK")
""")


def test_two_step_collective_multidevice():
    """shard_map two-step == flat all-reduce == numpy, on a 2x4 fake mesh."""
    r = subprocess.run([sys.executable, "-c", _SPMD_SCRIPT],
                       capture_output=True, text=True,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"},
                       cwd=__import__("os").path.join(
                           __import__("os").path.dirname(__file__), ".."))
    assert "SPMD_AGG_OK" in r.stdout, r.stdout + r.stderr


def test_compressed_two_step_unbiased():
    """int8 cross-pod hop is unbiased over repetitions (property)."""
    from repro.core.aggregation import _quantize_int8
    x = jnp.linspace(-2, 2, 511)
    outs = []
    for i in range(32):
        q, s = _quantize_int8(x, jax.random.PRNGKey(i))
        outs.append(np.asarray(q, np.float32) * float(s))
    assert abs(np.mean(outs) - np.mean(np.asarray(x))) < 5e-3
