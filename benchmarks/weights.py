"""The benchmark's own weights: made on the device, in one jitted call, from
the seed, in the type they are trained or served in.

The tree's layout is the family's (``family.weights.leaf_shapes``: the
layout the program's model takes is the program's interface).  The values are
the benchmark's, so the program and the plain reference are handed the same
inputs and neither takes anything the other has made.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from reference import cfg_items


def seed_key(seed: int):
    """A key for any whole-number seed (the driver's pass 2**31)."""
    return jax.random.key(int(seed) % (1 << 63))


@functools.partial(jax.jit,
                   static_argnames=("leaf_shapes", "cfg_items", "dtype"))
def _make(key, leaf_shapes, cfg_items, dtype):
    spec = leaf_shapes(dict(cfg_items))
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=is_leaf)
    keys = jax.random.split(key, len(leaves))
    made = []
    for k, (shape, fan) in zip(keys, leaves):
        if fan is None:
            # norm scales near one, not all equal: a reference that
            # dropped a norm's scale would otherwise go unnoticed
            w = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif fan == "embed":
            w = 0.02 * jax.random.normal(k, shape, jnp.float32)
        else:
            w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan)
        made.append(w.astype(dtype))
    return jax.tree.unflatten(treedef, made)


def make_params(family, seed: int, cfg: dict, dtype=jnp.float32):
    """The parameter tree of ``family`` for ``cfg`` (the configuration
    file's dict)."""
    return _make(seed_key(seed), family.weights.leaf_shapes, cfg_items(cfg),
                 jnp.dtype(dtype).name)
