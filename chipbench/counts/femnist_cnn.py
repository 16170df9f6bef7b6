"""Operations and bytes of the LEAF CNN's rounds, counted from shapes.

Operations are model operations, multiply-adds counted twice, taps on
the zero padding left out: the forward pass and the backward pass
(weight gradients everywhere, input gradients for every layer but the
first, whose input is data) of the involved clients' H x batch samples;
padding rows and eval are left out. Bytes are a floor on HBM traffic: in
every local step each client's parameters are read and the updated ones
written at least once (a chunk of 16 clients' parameters is far larger
than the chip's on-chip memory), and its minibatch is read once.
"""
from __future__ import annotations

IMG = 28
N_CLASSES = 62


def _taps(n: int, k: int = 5) -> int:
    """Kernel taps that fall inside the image, summed over the output
    positions of one axis of a SAME convolution (padding adds none)."""
    half = k // 2
    return sum(min(i + half, n - 1) - max(i - half, 0) + 1 for i in range(n))


def _layers(widths: dict):
    """(multiply-adds of one sample's forward pass, input gets a gradient)
    per layer."""
    c1, c2 = widths["cnn_channels"]
    fc = widths["cnn_fc"]
    feat = (IMG // 4) ** 2 * c2
    return [(_taps(IMG) ** 2 * 1 * c1, False),
            (_taps(IMG // 2) ** 2 * c1 * c2, True),
            (feat * fc, True),
            (fc * N_CLASSES, True)]


def params(widths: dict) -> int:
    c1, c2 = widths["cnn_channels"]
    fc = widths["cnn_fc"]
    feat = (IMG // 4) ** 2 * c2
    return (25 * c1 + c1 + 25 * c1 * c2 + c2 + feat * fc + fc
            + fc * N_CLASSES + N_CLASSES)


def forward_flops(widths: dict) -> float:
    return float(sum(2 * macs for macs, _ in _layers(widths)))


def train_flops(widths: dict) -> float:
    """One sample's forward and backward operations."""
    return float(sum(2 * macs * (3 if dx else 2)
                     for macs, dx in _layers(widths)))


def round_work(config: dict, traffic: dict, rec: dict) -> dict:
    """Model operations and the byte floor of one round's local training."""
    widths = config["widths"]
    steps = int(rec["involved"]) * traffic["local_steps"]
    batch = traffic["local_batch"]
    return {"flops": steps * batch * train_flops(widths),
            "bytes": steps * (2 * 4 * params(widths)
                              + batch * (IMG * IMG * 4 + 4))}
