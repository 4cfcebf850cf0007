"""What every architecture's plain reference (``bench/arch/<model_type>.py``
``logits_at``) is built from: float32 jnp with no kernels, cache or
batching, importing nothing of the program.

``by_layer`` runs a model layer by layer: each layer's weights are
rebuilt alone from the seed, applied to every sequence and dropped, so
that the reference fits beside what the run leaves on the device.  The
operations an architecture's layer is written in are here too: matrix
products, RMSNorm with a learned scale, rotary embedding on interleaved
pairs (dims 2i, 2i+1), the embedding and the untied LM head.

``precision="fp8"`` is the control: every matrix product's operands are
rounded to float8 e4m3 first, the step below the bf16 weights the
configuration serves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512           # query rows per attention block
_LEN_BLOCK = 512        # sequences pad to a multiple of this


def _round(x, precision):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def mm(a, b, eq, precision):
    return jnp.einsum(eq, _round(a, precision), _round(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def rmsnorm(w, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x [T, N, Dh]; rotate interleaved pairs by position."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos.astype(jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1).reshape(x.shape)


def lin(p, x, eq, precision):
    y = mm(x, p["w"].astype(jnp.float32), eq, precision)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _logits(o, x, rows, *, eps, precision):
    o = jax.tree.map(lambda a: a.astype(jnp.float32), o)
    h = rmsnorm(o["final_norm"]["w"], x[rows], eps)
    return mm(h, o["lm_head"]["w"], "td,dv->tv", precision)


@jax.jit
def _embed(table, toks):
    return table[toks].astype(jnp.float32)


def by_layer(outer: dict, layer, n_layers: int, block,
             seqs: list[list[int]], rows: list[list[int]], *, eps: float,
             precision: str) -> list[np.ndarray]:
    """Logits [len(rows[i]), V] at positions ``rows[i]`` of each token
    sequence ``seqs[i]``.  ``outer`` holds the embedding, final norm and
    LM head (``embed``, ``final_norm``, ``lm_head``); ``layer(li)``
    rebuilds layer ``li``'s weights, and ``block(p, x)`` applies them to
    one sequence, x f32 [T, D] with T padded to a multiple of 512."""
    xs = []
    for s in seqs:
        t = -(-len(s) // _LEN_BLOCK) * _LEN_BLOCK
        xs.append(_embed(_round(outer["embed"]["w"].astype(jnp.float32),
                                precision),
                         jnp.asarray(np.pad(np.asarray(s, np.int32),
                                            (0, t - len(s))))))
    for li in range(n_layers):
        p = layer(li)
        xs = [block(p, x) for x in xs]
        del p
    return [np.asarray(_logits(outer, x,
                               jnp.asarray(np.asarray(r, np.int32)),
                               eps=eps, precision=precision))
            for x, r in zip(xs, rows)]
