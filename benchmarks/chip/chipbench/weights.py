"""Weights made by the benchmark, on the device, from the seed.

The program's own init is not used: the reference reads these same arrays,
and takes nothing the program made. One jitted call fills every leaf of the
program's parameter tree (shapes and dtypes from ``jax.eval_shape`` of its
init) in the dtype it is served in. A leaf is filled by its name; a leaf
with no rule is an error, so a change of the program's layout shows here.

A matrix leaf is scaled by its fan-in, the first axis after the axes it
stacks over (layers, positions in a period, experts). How many those are
is the tier kind's ``stack_axes(names, shape)`` where the kind has one,
else ``stack_axes`` here: one under ``layers``, none elsewhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _names(path) -> list:
    return [getattr(k, "key", getattr(k, "idx", None)) for k in path]


def stack_axes(names, shape) -> int:
    """The default count of a leaf's stack axes: its layer axis, where it
    lies under ``layers``."""
    return 1 if "layers" in names else 0


def _leaf(key, names, shape, dtype, embed_std, n_stack):
    name = names[-1]
    if name == "table" or names == ["embed"]:
        return jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype) \
            * jnp.asarray(embed_std, dtype)
    if name == "scale":
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if name == "rel_bias":
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                       ).astype(dtype)
    if name == "D":
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)
    if name == "dt_bias":
        dt = jax.random.uniform(key, shape, jnp.float32, 1e-3, 0.1)
        return jnp.log(jnp.expm1(dt)).astype(dtype)
    if name == "conv_w":
        return jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype) \
            * jnp.asarray(0.2, dtype)
    if len(shape) >= 2 + n_stack:
        fan_in = shape[n_stack]
        return jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype) \
            * jnp.asarray(fan_in ** -0.5, dtype)
    raise ValueError(f"no weight rule for leaf {'/'.join(map(str, names))} "
                     f"{shape}")


def make_params(shapes, key, embed_std: float = 0.02, stack=stack_axes):
    """Fill the tree ``shapes`` (of ShapeDtypeStruct) from ``key`` in one
    jitted call on the default device. ``embed_std``: the scale of the
    token embedding (truncated normal); ``stack(names, shape)``: how many
    leading axes a leaf stacks over."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def fill(key):
        leaves = []
        for i, (path, s) in enumerate(flat):
            names, shape = _names(path), tuple(s.shape)
            leaves.append(_leaf(jax.random.fold_in(key, i), names, shape,
                                s.dtype, embed_std, stack(names, shape)))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.block_until_ready(jax.jit(fill)(key))


def seed_key(seed: int, purpose: int):
    """A JAX key for one purpose of one run, from a seed of any size."""
    words = np.random.default_rng([seed % (1 << 64), 7, purpose]).integers(
        0, 2 ** 31, 2)
    return jax.random.fold_in(jax.random.PRNGKey(int(words[0])),
                              int(words[1]))

