"""The paper's FEMNIST model: LEAF's CNN — two 5x5 conv layers (+ maxpool),
one dense layer, 62-way classifier (Caldas et al., LEAF; McMahan FedAvg).

This is the model the SFL reproduction trains end-to-end on CPU. The paper
states 26.416 Mbit of update traffic per client per round; the PON simulator
uses that constant (``pon.timing.MODEL_UPDATE_MBITS``) so the network-side
reproduction matches the paper's numbers exactly regardless of float width.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.layers import dense
from repro.models.param import ParamBuilder


def femnist_config() -> ModelConfig:
    return ModelConfig(
        name="femnist_cnn", family="cnn", n_layers=2, d_model=0, n_heads=0,
        n_kv_heads=0, d_ff=0, vocab_size=0, dtype="float32",
        img_size=28, n_classes=62, cnn_channels=(32, 64), cnn_fc=2048,
    )


def init_params(cfg: ModelConfig, key=None, abstract: bool = False, tp: int = 16):
    if key is None and not abstract:
        key = jax.random.PRNGKey(0)
    pb = ParamBuilder(key, jnp.dtype(cfg.dtype), abstract)
    c1, c2 = cfg.cnn_channels
    # He-init: ParamBuilder std = scale/sqrt(shape[0]); conv fan-in is 25*c_in
    pb.param("conv1_w", (5, 5, 1, c1), ("conv", "conv", None, None), scale=0.63)
    pb.param("conv1_b", (c1,), (None,), init="zeros")
    pb.param("conv2_w", (5, 5, c1, c2), ("conv", "conv", None, None),
             scale=0.11 * np.sqrt(32.0 / c1))
    pb.param("conv2_b", (c2,), (None,), init="zeros")
    feat = (cfg.img_size // 4) ** 2 * c2
    pb.param("fc1_w", (feat, cfg.cnn_fc), ("mlp", None), scale=1.0)
    pb.param("fc1_b", (cfg.cnn_fc,), (None,), init="zeros")
    pb.param("fc2_w", (cfg.cnn_fc, cfg.n_classes), (None, "classes"), scale=1.0)
    pb.param("fc2_b", (cfg.n_classes,), (None,), init="zeros")
    return pb.build()


def _conv(x, w, b):
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + b


def _maxpool(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def apply(params, images):
    """images: (B, 28, 28, 1) float32 -> logits (B, 62)."""
    x = jax.nn.relu(_conv(images, params["conv1_w"], params["conv1_b"]))
    x = _maxpool(x)
    x = jax.nn.relu(_conv(x, params["conv2_w"], params["conv2_b"]))
    x = _maxpool(x)
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(dense(x, params["fc1_w"]) + params["fc1_b"])
    return dense(x, params["fc2_w"]) + params["fc2_b"]


def loss_fn(params, batch, cfg=None, rules=None):
    logits = apply(params, batch["images"])
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), axis=1)[:, 0]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(nll)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    acc = jnp.sum((jnp.argmax(logits, -1) == labels) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss, {"acc": acc}
