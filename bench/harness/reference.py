"""The plain reference: a dense-GQA decoder forward in float32 jnp, QKV
bias included, with no kernels, cache or batching.  It rebuilds the
weights from the seed one layer at a time (``weights.one_layer``) and
imports nothing of the program.

Its equations are the program's model (``repro.models.transformer``):
RMSNorm with a learned scale, rotary embedding on interleaved pairs
(dims 2i, 2i+1), causal grouped-query attention (query head h reads KV
head h // (H / K)), SwiGLU MLP, untied LM head.

``precision="fp8"`` is the control: every matrix product's operands are
rounded to float8 e4m3 first, the step below the bf16 weights the
configuration serves.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

_Q_BLOCK = 512          # query rows per attention block
_LEN_BLOCK = 512        # sequences pad to a multiple of this


def _round(x, precision):
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _mm(a, b, eq, precision):
    return jnp.einsum(eq, _round(a, precision), _round(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(w, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x [T, N, Dh]; rotate interleaved pairs by position."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos.astype(jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c], -1).reshape(x.shape)


def _lin(p, x, eq, precision):
    y = _mm(x, p["w"].astype(jnp.float32), eq, precision)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y


@functools.partial(jax.jit, static_argnames=("eps", "theta", "precision"))
def block(p, x, *, eps, theta, precision):
    """One decoder layer over one sequence: x f32 [T, D] -> [T, D]."""
    t = x.shape[0]
    pos = jnp.arange(t)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    a = p["attn"]
    h = _rmsnorm(p["ln1"]["w"], x, eps)
    q = _rope(_lin(a["wq"], h, "td,dhe->the", precision), pos, theta)
    k = _rope(_lin(a["wk"], h, "td,dke->tke", precision), pos, theta)
    v = _lin(a["wv"], h, "td,dke->tke", precision)
    n_h, n_kv, dh = q.shape[1], k.shape[1], q.shape[2]
    q = q.reshape(t, n_kv, n_h // n_kv, dh) / jnp.sqrt(jnp.float32(dh))

    def attend(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * _Q_BLOCK, _Q_BLOCK)
        s = _mm(qb, k, "qkgd,tkd->kgqt", precision)
        qpos = i * _Q_BLOCK + jnp.arange(_Q_BLOCK)
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return _mm(w, v, "kgqt,tkd->qkgd", precision)

    ctx = jax.lax.map(attend, jnp.arange(t // _Q_BLOCK))
    ctx = ctx.reshape(t, n_h, dh)
    x = x + _mm(ctx, a["wo"]["w"], "the,heD->tD", precision)
    h = _rmsnorm(p["ln2"]["w"], x, eps)
    f = p["ffn"]
    g = jax.nn.silu(_lin(f["gate"], h, "td,df->tf", precision))
    u = _lin(f["up"], h, "td,df->tf", precision)
    return x + _mm(g * u, f["down"]["w"], "tf,fd->td", precision)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _logits(o, x, rows, *, eps, precision):
    o = jax.tree.map(lambda a: a.astype(jnp.float32), o)
    h = _rmsnorm(o["final_norm"]["w"], x[rows], eps)
    return _mm(h, o["lm_head"]["w"], "td,dv->tv", precision)


@jax.jit
def _embed(table, toks):
    return table[toks].astype(jnp.float32)


def logits_at(dims: dict, seed: int, seqs: list[list[int]],
              rows: list[list[int]], precision: str = "f32"
              ) -> list[np.ndarray]:
    """Logits [len(rows[i]), V] at positions ``rows[i]`` of each token
    sequence ``seqs[i]``, computed layer by layer: each layer's weights
    are rebuilt once and applied to every sequence."""
    o = weights.outer_params(dims, seed)
    xs = []
    for s in seqs:
        t = -(-len(s) // _LEN_BLOCK) * _LEN_BLOCK
        xs.append(_embed(_round(o["embed"]["w"].astype(jnp.float32),
                                precision),
                         jnp.asarray(np.pad(np.asarray(s, np.int32),
                                            (0, t - len(s))))))
    for li in range(dims["n_layers"]):
        p = weights.one_layer(dims, seed, li)
        xs = [block(p, x, eps=dims["norm_eps"], theta=dims["rope_theta"],
                    precision=precision) for x in xs]
        del p
    return [np.asarray(_logits(o, x, jnp.asarray(np.asarray(r, np.int32)),
                               eps=dims["norm_eps"], precision=precision))
            for x, r in zip(xs, rows)]
