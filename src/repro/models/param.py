"""Parameter definition pytrees.

Modules describe their parameters as pytrees of :class:`ParamDef` (shape +
logical axes + initializer). The same definition tree serves three uses:

- ``materialize``   — real arrays for smoke tests / examples / training;
- ``abstract``      — ``jax.ShapeDtypeStruct`` stand-ins for the dry-run
                      (no allocation; the pattern the assignment requires);
- ``partition_specs`` — ``PartitionSpec`` per param from logical-axis rules
                      with divisibility fallback (runtime/sharding.py).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    logical_axes: Tuple[Optional[str], ...]
    init: str = "normal"  # normal | zeros | ones | a_log | dt_bias
    std: Optional[float] = None  # None => fan-in 1/sqrt(shape[-2 or -1])
    dtype: Optional[str] = None

    def __post_init__(self):
        assert len(self.shape) == len(self.logical_axes), (self.shape, self.logical_axes)


def is_def(x) -> bool:
    return isinstance(x, ParamDef)


def stack_defs(defs, repeats: int):
    """Add a leading scanned-layers axis to every ParamDef in a tree."""
    def add(d: ParamDef) -> ParamDef:
        return dataclasses.replace(
            d, shape=(repeats,) + d.shape, logical_axes=("layers",) + d.logical_axes)
    return jax.tree.map(add, defs, is_leaf=is_def)


def _fan_in_std(d: ParamDef) -> float:
    if d.std is not None:
        return d.std
    if len(d.shape) >= 2:
        fan_in = d.shape[-2]
    else:
        fan_in = d.shape[-1]
    return 1.0 / math.sqrt(max(fan_in, 1))


@functools.partial(jax.jit, static_argnames=("d", "dt"))
def _init_leaf(k, *, d: ParamDef, dt: str):
    """One leaf, drawn in fp32 and cast in a single compiled program, so
    the fp32 draw fuses into the cast and never exists whole on the
    device (a stacked published-width MLP leaf would need GBs)."""
    dt = jnp.dtype(dt)
    if d.init == "zeros":
        return jnp.zeros(d.shape, dt)
    if d.init == "ones":
        return jnp.ones(d.shape, dt)
    if d.init == "a_log":  # mamba2: A in [1, 16), stored as log
        u = jax.random.uniform(k, d.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(dt)
    if d.init == "dt_bias":  # softplus^-1 of dt ~ U[1e-3, 1e-1]
        u = jax.random.uniform(k, d.shape, jnp.float32, 1e-3, 1e-1)
        return jnp.log(jnp.expm1(u)).astype(dt)
    std = _fan_in_std(d)
    return (jax.random.normal(k, d.shape, jnp.float32) * std).astype(dt)


def materialize(defs, key: jax.Array, default_dtype: str = "bfloat16"):
    """Initialize real parameter arrays from a def tree."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_def)
    keys = jax.random.split(key, max(len(leaves), 1))
    return jax.tree.unflatten(treedef, [
        _init_leaf(k, d=d, dt=d.dtype or default_dtype)
        for d, k in zip(leaves, keys)])


def abstract(defs, default_dtype: str = "bfloat16", shardings=None):
    """ShapeDtypeStruct tree (optionally with shardings attached)."""
    if shardings is None:
        return jax.tree.map(
            lambda d: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype or default_dtype)),
            defs, is_leaf=is_def)
    return jax.tree.map(
        lambda d, s: jax.ShapeDtypeStruct(d.shape, jnp.dtype(d.dtype or default_dtype), sharding=s),
        defs, shardings, is_leaf=is_def)


def count_params(defs) -> int:
    leaves = jax.tree.leaves(defs, is_leaf=is_def)
    return int(sum(int(np.prod(d.shape)) for d in leaves))
