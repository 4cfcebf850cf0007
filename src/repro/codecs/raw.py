"""Raw (uncompressed) fallback codec — LCP's exception page, whole-page.

Stores every page verbatim and reports compressed size == raw size, so
the engines' compression ratio is exactly 1.0.  Its job is to prove the
framework's degenerate case stays sound end to end: CAMP preemption
values, SIP retention ranking, and the warm==cold canonical-prefix
contract all hold when nothing compresses — and, being trivially
``lossless``, it exercises the identity fast path that skips the
prefill-side canonical roundtrip (the cheap win the codec API makes
expressible).

Pool storage is f32 (the exact scratch values); byte accounting uses
the model's bf16 element width so the reported ratio is raw/raw = 1.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .base import PageCodec, register


class RawKVPages(NamedTuple):
    k: jax.Array    # f32 [P, KVH, page, D]
    v: jax.Array


class RawCodec(PageCodec):
    name = "raw"
    lossless = True

    def init_pools(self, n_pages, kvh, page, dh):
        # distinct buffers per field: the engines donate the pool pytree
        # into jitted updates, and aliased leaves would donate twice
        shp = (n_pages, kvh, page, dh)
        return RawKVPages(jnp.zeros(shp, jnp.float32),
                          jnp.zeros(shp, jnp.float32))

    def compress_kv_pages(self, k, v):
        return RawKVPages(k.astype(jnp.float32), v.astype(jnp.float32))

    def decompress_pages(self, pages):
        return pages.k, pages.v

    def page_nbytes(self, pages) -> jax.Array:
        kvh, page, d = pages.k.shape[1:]
        n = pages.k.shape[0]
        return jnp.full((n,), 2 * 2 * kvh * page * d, jnp.int32)


RAW = register(RawCodec())
