"""Seeded random weights in the served type (bf16; norm weights f32): the
helpers every architecture module (``bench/arch/``) draws its tree with.

Every leaf is drawn from its own key, ``leaf_key(key, leaf, layer)`` =
``fold_in(fold_in(seed, leaf), layer)``, so the plain reference can
rebuild any one layer alone from the seed and never reads what the
program holds.  ``on_device`` makes a function that builds (part of) the
tree one jitted call on the device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key for any non-negative seed, also one above 2**31."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def leaf_key(key, leaf: int, layer) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(key, leaf), layer)


def normal(key, shape, scale, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def norm(key, d):
    return 1.0 + 0.1 * jax.random.normal(key, (d,), jnp.float32)


def on_device(build):
    """``build(dims, key, *args)`` as ``f(dims, seed, *args)``: one jitted
    call on the device, the dims (hashable values) static, the key drawn
    from the seed."""

    def jitted(dims_items: tuple, key, *args):
        return build(dict(dims_items), key, *args)

    jitted.__name__ = jitted.__qualname__ = build.__name__  # program name
    jitted = jax.jit(jitted, static_argnums=0)

    def call(dims: dict, seed: int, *args):
        return jitted(tuple(sorted(dims.items())), seed_key(seed), *args)

    return call
