"""Seeded weights of a cell's model, made on the device in one jitted call.

The benchmark makes the weights; the program and the reference are each
handed them.  Which leaves there are, and their shapes, is the model
family's (``bench/families/<family>.py::shapes``).

Matrices are truncated normals (two standard deviations) of standard
deviation ``1/sqrt(fan_in)``; norm gains are offsets from 1 and start at 0.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

import families

Shapes = Dict[str, Any]


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, also beyond 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)


def shapes(config: Dict[str, Any]) -> Shapes:
    """Leaf shapes with their fan-in axis (None for norm gains)."""
    return families.load(config).shapes(config)


def _leaf(key, spec: Tuple, dtype) -> jax.Array:
    shape, fan_axis = spec
    if fan_axis is None:
        return jnp.zeros(shape, dtype)
    std = shape[fan_axis] ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def make(config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The weights for ``seed``, in ``config["param_dtype"]``."""
    spec = shapes(config)
    dtype = jnp.dtype(config["param_dtype"])
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))

    @jax.jit
    def build(key):
        return jax.tree.unflatten(treedef, [
            _leaf(jax.random.fold_in(key, i), s, dtype)
            for i, s in enumerate(leaves)])

    return build(seed_key(seed))
