"""Declarative parameter tables.

Models declare parameters as ``ParamDef`` entries (shape + logical axes +
init law).  From one table the framework derives, without ever allocating
the full tensors:

* ``init_params``      -- materialized weights (smoke tests, examples),
* ``shape_structs``    -- ShapeDtypeStruct tree for the multi-pod dry-run
                          (340B-parameter models never touch device memory),
* ``partition_specs``  -- PartitionSpec tree via the sharding rules engine,
* ``param_count``      -- exact parameter count for roofline MODEL_FLOPS.

This is the mechanism that lets the EASEY BuildService treat a model like
the paper treats a Dockerfile: a portable description that is *compiled
for* a target rather than edited by the user.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding.rules import AxisRules, DEFAULT_RULES, logical_to_spec


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    logical_axes: tuple[str | None, ...]
    dtype: Any = jnp.bfloat16
    init: str = "normal"        # normal | zeros | ones | embed
    scale: float | None = None  # None -> fan-in 1/sqrt(fan_in)

    def __post_init__(self):
        if len(self.shape) != len(self.logical_axes):
            raise ValueError(
                f"shape {self.shape} vs logical axes {self.logical_axes}")

    @property
    def size(self) -> int:
        return math.prod(self.shape)


ParamTable = dict  # nested dict[str, ParamDef | ParamTable]


def _map_table(table: ParamTable, fn: Callable[[ParamDef], Any]):
    out = {}
    for k, v in table.items():
        out[k] = fn(v) if isinstance(v, ParamDef) else _map_table(v, fn)
    return out


def param_count(table: ParamTable) -> int:
    total = 0
    for v in jax.tree.leaves(_map_table(table, lambda d: d.size)):
        total += v
    return total


@functools.partial(jax.jit, static_argnums=(1, 3))
def _normal_leaf(key: jax.Array, shape: tuple[int, ...], scale: jax.Array,
                 dtype) -> jax.Array:
    # one jitted program per leaf: the float32 draw fuses into the cast,
    # so a stacked full-width leaf never holds a float32 copy of itself.
    # The (no-op) reduce_precision keeps XLA from folding `scale` into
    # the draw's own sqrt(2) factor, so the bits equal the eager
    # draw-then-scale that earlier weights were made with
    x = jax.lax.reduce_precision(jax.random.normal(key, shape, jnp.float32),
                                 exponent_bits=8, mantissa_bits=23)
    return (x * scale).astype(dtype)


def init_params(table: ParamTable, rng: jax.Array, dtype=None):
    """Materialize weights from ``rng`` (smoke configs on the CPU, and
    full-width configs on an accelerator: the peak is the weights plus one
    program's temporaries, not a float32 copy of the largest leaf)."""
    leaves, treedef = jax.tree.flatten(
        _map_table(table, lambda d: d), is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(rng, len(leaves))
    out = []
    for key, d in zip(keys, leaves):
        dt = dtype or d.dtype
        if d.init == "zeros":
            out.append(jnp.zeros(d.shape, dt))
        elif d.init == "ones":
            out.append(jnp.ones(d.shape, dt))
        else:
            if d.scale is not None:
                scale = d.scale
            elif d.init == "embed":
                scale = 1.0
            else:
                fan_in = d.shape[0] if len(d.shape) >= 2 else max(d.shape[-1], 1)
                scale = 1.0 / math.sqrt(fan_in)
            out.append(_normal_leaf(key, d.shape, jnp.float32(scale), dt))
    return jax.tree.unflatten(treedef, out)


def shape_structs(table: ParamTable, dtype=None):
    return _map_table(
        table, lambda d: jax.ShapeDtypeStruct(d.shape, dtype or d.dtype))


def partition_specs(table: ParamTable, mesh: Mesh,
                    rules: AxisRules | None = None,
                    fallbacks: list[str] | None = None):
    rules = rules or DEFAULT_RULES
    return _map_table(
        table,
        lambda d: NamedSharding(
            mesh, logical_to_spec(d.logical_axes, d.shape, mesh, rules, fallbacks)),
    )


def bytes_of(tree) -> int:
    """Total bytes of a pytree of arrays or ShapeDtypeStructs."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        total += math.prod(leaf.shape) * np.dtype(leaf.dtype).itemsize
    return total


def replicated_specs(tree, mesh: Mesh):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
