"""Device meshes.

``make_test_mesh`` is a FUNCTION (never module-level) so importing this
module touches no jax device state: it builds a mesh over the first
``prod(shape)`` devices JAX sees, the chips on a TPU host or the host
devices on the CPU (``--xla_force_host_platform_device_count``).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def make_test_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """A mesh of ``shape`` named ``axes`` over the first devices."""
    import jax
    from jax.sharding import Mesh

    n = int(np.prod(shape))
    devs = np.array(jax.devices()[:n]).reshape(shape)
    return Mesh(devs, axes)
