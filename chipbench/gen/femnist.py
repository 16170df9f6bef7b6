"""FEMNIST-like federated data, generated on the host from a seed.

A copy of the program's ``repro.data.femnist`` generator (the original
stays where it is), kept here so that no change to the program can change
what the benchmark feeds it. Class-conditional 28x28 images of 62 classes,
a per-writer style shift and a Dirichlet label skew per client, log-normal
sample counts (tens to hundreds) as in LEAF.
"""
from __future__ import annotations

import numpy as np

N_CLASSES = 62
IMG = 28
MEAN_SAMPLES = 120.0
DIRICHLET_ALPHA = 0.25
NOISE = 0.8
PROTO_RANK = 16
EVAL_PER_CLASS = 8


def _class_prototypes(rng: np.random.Generator) -> np.ndarray:
    from scipy.signal import convolve2d
    basis = rng.normal(0, 1, size=(PROTO_RANK, IMG, IMG))
    k = np.outer(np.hanning(7), np.hanning(7))
    k /= k.sum()
    basis = np.stack([convolve2d(b, k, mode="same") for b in basis])
    coef = rng.normal(0, 1, size=(N_CLASSES, PROTO_RANK))
    coef *= (rng.random((N_CLASSES, PROTO_RANK)) < 0.4)
    protos = np.einsum("cr,rxy->cxy", coef, basis)
    protos /= protos.std(axis=(1, 2), keepdims=True) + 1e-9
    return protos.astype(np.float32)


def generate(n_clients: int, seed: int):
    """Returns ``(clients, eval_set)``: a list of ``{"images": (k, 28, 28,
    1) f32, "labels": (k,) i32}`` and one global eval set of the same
    form, 8 images per class."""
    rng = np.random.default_rng(seed)
    protos = _class_prototypes(rng)
    counts = np.maximum(20, rng.lognormal(np.log(MEAN_SAMPLES), 0.4,
                                          n_clients).astype(int))
    clients = []
    for c in range(n_clients):
        k = int(counts[c])
        label_p = rng.dirichlet(np.full(N_CLASSES, DIRICHLET_ALPHA))
        labels = rng.choice(N_CLASSES, size=k, p=label_p)
        shift = (int(rng.integers(-2, 3)), int(rng.integers(-2, 3)))
        gain = float(rng.uniform(0.8, 1.2))
        imgs = np.roll(protos[labels], shift=shift, axis=(1, 2)) * gain
        imgs = imgs + rng.normal(0, NOISE, imgs.shape)
        clients.append({"images": imgs[..., None].astype(np.float32),
                        "labels": labels.astype(np.int32)})
    el, ei = [], []
    for cls in range(N_CLASSES):
        imgs = protos[np.full(EVAL_PER_CLASS, cls)] + rng.normal(
            0, NOISE, (EVAL_PER_CLASS, IMG, IMG))
        el.append(np.full(EVAL_PER_CLASS, cls))
        ei.append(imgs)
    eval_set = {"images": np.concatenate(ei)[..., None].astype(np.float32),
                "labels": np.concatenate(el).astype(np.int32)}
    return clients, eval_set


def sample_counts(clients) -> np.ndarray:
    return np.array([len(c["labels"]) for c in clients], np.float32)


def client_minibatches(rng: np.random.Generator, client, steps: int,
                       batch: int):
    """(steps, batch, ...) minibatch stack for one client's local epoch."""
    idx = rng.integers(0, len(client["labels"]), size=(steps, batch))
    return {"images": client["images"][idx], "labels": client["labels"][idx]}
