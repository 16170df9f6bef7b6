"""The benchmark's operation counts against XLA's own count (CPU)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from chipbench_tiny import ROOT, load_module

counts = load_module(ROOT / "chipbench/counts/femnist_cnn.py")
WIDTHS = {"cnn_channels": [32, 64], "cnn_fc": 2048}
BATCH = 10


def test_parameter_count_is_the_published_one():
    assert counts.params(WIDTHS) == 6_603_710


@pytest.mark.parametrize("kind", ["forward", "train"])
def test_cnn_count_matches_xla(kind):
    """One full-width client step at the cell's batch: the model
    operations counted from shapes are XLA's to within 2% (XLA adds the
    elementwise work: activations, pooling, softmax, bias adds)."""
    from chipbench.reference import femnist_cnn as cnn

    params = jax.eval_shape(lambda k: cnn.init(WIDTHS, k),
                            jax.random.PRNGKey(0))
    images = jax.ShapeDtypeStruct((BATCH, 28, 28, 1), jnp.float32)
    labels = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    fn = cnn.loss if kind == "forward" else jax.grad(cnn.loss)
    cost = jax.jit(fn).lower(params, images, labels).compile().cost_analysis()
    xla = cost["flops"]
    ours = BATCH * (counts.forward_flops(WIDTHS) if kind == "forward"
                    else counts.train_flops(WIDTHS))
    assert ours <= xla <= 1.02 * ours, (ours, xla)
