"""Parameter builder: keeps arrays and their logical sharding axes in one
structure so init code cannot drift from sharding specs.

Params are plain nested dicts of jnp arrays; a parallel dict of logical-axis
tuples is built by the same calls. ``abstract=True`` builds
ShapeDtypeStructs instead of allocating (used by ``launch.specs`` to lower
steps without spending host RAM on the parameter tree).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class ParamBuilder:
    def __init__(self, key: Optional[jax.Array], dtype, abstract: bool = False):
        self._key = key
        self.dtype = dtype
        self.abstract = abstract
        self.params: dict = {}
        self.logical: dict = {}

    def _next_key(self):
        if self.abstract:
            return None
        self._key, sub = jax.random.split(self._key)
        return sub

    def param(self, name: str, shape: Tuple[int, ...], logical: Tuple[Optional[str], ...],
              init: str = "normal", scale: float = 1.0, dtype=None):
        assert len(shape) == len(logical), (name, shape, logical)
        dtype = dtype or self.dtype
        if self.abstract:
            arr = jax.ShapeDtypeStruct(shape, dtype)
        else:
            k = self._next_key()
            if init == "normal":
                std = scale / np.sqrt(max(1, shape[0] if len(shape) else 1))
                arr = (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
            elif init == "zeros":
                arr = jnp.zeros(shape, dtype)
            elif init == "ones":
                arr = jnp.ones(shape, dtype)
            elif init == "uniform":
                arr = (jax.random.uniform(k, shape, jnp.float32, -scale, scale)).astype(dtype)
            elif init == "linspace":  # for per-channel decay init (rwkv/rglru)
                arr = jnp.linspace(-scale, scale, int(np.prod(shape)), dtype=jnp.float32
                                   ).reshape(shape).astype(dtype)
            else:
                raise ValueError(init)
        self.params[name] = arr
        self.logical[name] = logical
        return arr

    def sub(self, name: str) -> "ParamBuilder":
        child = ParamBuilder(None, self.dtype, self.abstract)
        child._parent = self  # keep key flowing through parent
        child._next_key = self._next_key  # type: ignore
        self.params[name] = child.params
        self.logical[name] = child.logical
        return child

    def build(self):
        return self.params, self.logical


def stack_abstract(tree, n: int):
    """Add a leading stacked-layers axis of size n to an abstract tree."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n,) + tuple(x.shape), x.dtype)
        if isinstance(x, jax.ShapeDtypeStruct)
        else jnp.broadcast_to(x, (n,) + x.shape),
        tree,
    )


def stack_logical(tree):
    """Prefix every logical tuple with the 'layers' axis."""
    return jax.tree.map(
        lambda lg: ("layers",) + lg,
        tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
    )
