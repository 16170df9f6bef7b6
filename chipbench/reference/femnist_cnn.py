"""Plain reference of the LEAF FEMNIST CNN (Caldas et al., arXiv:1812.01097).

conv 5x5 (SAME, c1 channels) -> ReLU -> max-pool 2x2 -> conv 5x5 (c2) ->
ReLU -> max-pool 2x2 -> dense (fc) -> ReLU -> dense (62 classes), softmax
cross-entropy. Written from the published description in ``jax.numpy`` and
``jax.lax``, with nothing imported from the program. The parameter names
are the ones the program's loss function reads, so the benchmark can make
one set of weights and hand it to both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

N_CLASSES = 62
IMG = 28


def shapes(widths: dict) -> dict:
    c1, c2 = widths["cnn_channels"]
    fc = widths["cnn_fc"]
    feat = (IMG // 4) ** 2 * c2
    return {"conv1_w": (5, 5, 1, c1), "conv1_b": (c1,),
            "conv2_w": (5, 5, c1, c2), "conv2_b": (c2,),
            "fc1_w": (feat, fc), "fc1_b": (fc,),
            "fc2_w": (fc, N_CLASSES), "fc2_b": (N_CLASSES,)}


def init(widths: dict, key, dtype=jnp.float32) -> dict:
    """He-normal weights for the ReLU layers, LeCun-normal for the
    classifier, zero biases. Call under ``jax.jit`` to make them on the
    device in one program."""
    out = {}
    sh = shapes(widths)
    keys = jax.random.split(key, 4)
    for k, name in zip(keys, ("conv1_w", "conv2_w", "fc1_w", "fc2_w")):
        shape = sh[name]
        fan_in = 1
        for d in shape[:-1]:
            fan_in *= d
        gain = 1.0 if name == "fc2_w" else 2.0
        std = (gain / fan_in) ** 0.5
        out[name] = (jax.random.normal(k, shape, jnp.float32) * std
                     ).astype(dtype)
    for name in ("conv1_b", "conv2_b", "fc1_b", "fc2_b"):
        out[name] = jnp.zeros(sh[name], dtype)
    return out


def _conv(x, w, b):
    y = jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b.astype(x.dtype)


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def logits(params, images):
    x = images.astype(params["conv1_w"].dtype)
    x = _pool(jax.nn.relu(_conv(x, params["conv1_w"], params["conv1_b"])))
    x = _pool(jax.nn.relu(_conv(x, params["conv2_w"], params["conv2_b"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1_w"] + params["fc1_b"])
    return x @ params["fc2_w"] + params["fc2_b"]


def loss(params, images, labels):
    """Mean softmax cross-entropy, computed in float32 whatever the
    parameters' type."""
    logp = jax.nn.log_softmax(logits(params, images).astype(jnp.float32))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
