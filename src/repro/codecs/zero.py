"""Zero/repeated-value fast-path codec (LCP's zero-page case).

The paper's cheapest win: pages dominated by zero or repeated values
compress to almost nothing, with near-free (de)compression.  Per
(head, token) row this codec stores a one-byte class flag plus

  * **zero** rows  — nothing (the flag alone);
  * **rep**  rows  — one f32 repeated value;
  * everything else — the exact payload, LCP's *exception* story.

The roundtrip is the identity bit-for-bit (``lossless = True``): zero
rows decode to exact zeros, rep rows to their exact value, exceptions
to their exact payload — so the canonical-prefix contract degenerates
to "attend the exact values" and the engines skip the prefill-side
roundtrip entirely.

Byte accounting models the on-the-wire form at the model's bf16
element width (the raw baseline the engines report against): a zero
row costs 1 flag byte, a rep row 1 + 4, an exception row 1 + 2*D —
tiny pages for zero-heavy KV, slightly *above* raw for incompressible
pages (the flag overhead), which is exactly the honest signal CAMP and
SIP retention should see.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .base import PageCodec, register

F_ZERO, F_REP, F_RAW = 0, 1, 2


class ZeroRepKVPages(NamedTuple):
    """Flag + rep-value + exception-payload form, K and V sides."""
    kf: jax.Array   # int8 [P, KVH, page] row class (F_ZERO/F_REP/F_RAW)
    kc: jax.Array   # f32  [P, KVH, page] repeated value (0 unless F_REP)
    kx: jax.Array   # f32  [P, KVH, page, D] exact payload (0 unless F_RAW)
    vf: jax.Array
    vc: jax.Array
    vx: jax.Array


def _enc(x: jax.Array):
    x = x.astype(jnp.float32)
    first = x[..., 0]
    is_rep = jnp.all(x == first[..., None], axis=-1)   # incl. all-zero rows
    is_zero = is_rep & (first == 0.0)
    f = jnp.where(is_zero, F_ZERO,
                  jnp.where(is_rep, F_REP, F_RAW)).astype(jnp.int8)
    val = jnp.where(is_rep & ~is_zero, first, 0.0)
    payload = jnp.where((f == F_RAW)[..., None], x, 0.0)
    return f, val, payload


def _dec(f: jax.Array, val: jax.Array, payload: jax.Array) -> jax.Array:
    rep = jnp.broadcast_to(val[..., None], payload.shape)
    out = jnp.where((f == F_REP)[..., None], rep, payload)
    return jnp.where((f == F_ZERO)[..., None], 0.0, out)


class ZeroRepCodec(PageCodec):
    name = "zero"
    lossless = True

    def init_pools(self, n_pages, kvh, page, dh):
        # distinct buffers per field: the engines donate the pool pytree
        # into jitted updates, and aliased leaves would donate twice
        shp = (n_pages, kvh, page)

        def side():
            return (jnp.zeros(shp, jnp.int8),
                    jnp.zeros(shp, jnp.float32),
                    jnp.zeros(shp + (dh,), jnp.float32))

        return ZeroRepKVPages(*side(), *side())

    def compress_kv_pages(self, k, v):
        return ZeroRepKVPages(*_enc(k), *_enc(v))

    def decompress_pages(self, pages):
        return (_dec(pages.kf, pages.kc, pages.kx),
                _dec(pages.vf, pages.vc, pages.vx))

    def page_nbytes(self, pages) -> jax.Array:
        d = pages.kx.shape[-1]

        def side(f):
            row = jnp.where(f == F_ZERO, 1,
                            jnp.where(f == F_REP, 1 + 4, 1 + 2 * d))
            return jnp.sum(row, axis=(1, 2))

        return (side(pages.kf) + side(pages.vf)).astype(jnp.int32)


ZERO = register(ZeroRepCodec())
