"""Seeded random weights in the served type (bf16; norm weights f32).

Every leaf is drawn from its own key, ``fold_in(fold_in(seed, leaf),
layer)``, so the plain reference can rebuild any one layer alone from the
seed (``layer``) and never reads what the program holds.  ``model`` makes
the whole stacked tree in one jitted call on the device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_EMBED, _HEAD, _FINAL = 100, 101, 102


def seed_key(seed: int) -> jax.Array:
    """A key for any non-negative seed, also one above 2**31."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def _normal(key, shape, scale, dtype=jnp.bfloat16):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _norm(key, d):
    return 1.0 + 0.1 * jax.random.normal(key, (d,), jnp.float32)


def layer(dims: dict, key, li) -> dict:
    """Layer ``li``'s parameters, in the program's tree layout."""
    d, h, kv, dh, f = (dims["d_model"], dims["n_heads"], dims["n_kv_heads"],
                       dims["head_dim"], dims["d_ff"])

    def k(i):
        return jax.random.fold_in(jax.random.fold_in(key, i), li)

    attn = {"wq": {"w": _normal(k(1), (d, h, dh), d ** -0.5)},
            "wk": {"w": _normal(k(2), (d, kv, dh), d ** -0.5)},
            "wv": {"w": _normal(k(3), (d, kv, dh), d ** -0.5)},
            "wo": {"w": _normal(k(4), (h, dh, d), (h * dh) ** -0.5)}}
    if dims["qkv_bias"]:
        attn["wq"]["b"] = _normal(k(5), (h, dh), 0.25)
        attn["wk"]["b"] = _normal(k(6), (kv, dh), 0.25)
        attn["wv"]["b"] = _normal(k(7), (kv, dh), 0.25)
    return {"ln1": {"w": _norm(k(8), d)}, "ln2": {"w": _norm(k(9), d)},
            "attn": attn,
            "ffn": {"gate": {"w": _normal(k(10), (d, f), d ** -0.5)},
                    "up": {"w": _normal(k(11), (d, f), d ** -0.5)},
                    "down": {"w": _normal(k(12), (f, d), f ** -0.5)}}}


def outer(dims: dict, key) -> dict:
    """Embedding, final norm and LM head."""
    d, v = dims["d_model"], dims["vocab"]
    return {"embed": {"w": _normal(jax.random.fold_in(key, _EMBED), (v, d),
                                   1.0)},
            "final_norm": {"w": _norm(jax.random.fold_in(key, _FINAL), d)},
            "lm_head": {"w": _normal(jax.random.fold_in(key, _HEAD), (d, v),
                                     d ** -0.5)}}


@functools.partial(jax.jit, static_argnums=0)
def _model(dims_items: tuple, key) -> dict:
    dims = dict(dims_items)
    blocks = jax.vmap(lambda li: layer(dims, key, li))(
        jnp.arange(dims["n_layers"]))
    return dict(outer(dims, key), blocks=blocks)


def model(dims: dict, seed: int) -> dict:
    """The whole tree, layers stacked [L, ...], made on the device."""
    return _model(tuple(sorted(dims.items())), seed_key(seed))


@functools.partial(jax.jit, static_argnums=0)
def _layer(dims_items: tuple, key, li) -> dict:
    return layer(dict(dims_items), key, li)


def one_layer(dims: dict, seed: int, li: int) -> dict:
    return _layer(tuple(sorted(dims.items())), seed_key(seed), li)


@functools.partial(jax.jit, static_argnums=0)
def _outer(dims_items: tuple, key) -> dict:
    return outer(dict(dims_items), key)


def outer_params(dims: dict, seed: int) -> dict:
    return _outer(tuple(sorted(dims.items())), seed_key(seed))
