"""BDI page codec: the single-base B+Delta int8 row form (the default).

The thesis codec, unchanged — this module only *adapts* the existing
kernel surface (``kernels/ref.py`` oracle, ``kernels/ops.py`` Pallas
wrappers, ``kernels/paged_attention.py`` fused decode kernel) to the
:class:`~repro.codecs.base.PageCodec` protocol.  One row = one
(head, token) vector; base = the row's first element, scale = the
power-of-two covering the max residual, deltas int8.

Byte accounting is BDI-faithful: each row costs 8 bytes of base+scale
metadata plus D delta bytes — unless the row is all-zero (the paper's
ENC_ZERO case: metadata only, the delta bytes drop out).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.paged_attention import paged_attention_tail

from .base import PageCodec, register


class BDICodec(PageCodec):
    name = "bdi"
    lossless = False               # int8 quantization: |err| <= scale/2
    has_fused_kernels = True       # Pallas row codec + paged attention

    def init_pools(self, n_pages, kvh, page, dh):
        shp = (n_pages, kvh, page)
        return ref.CompressedKVPages(
            kd=jnp.zeros(shp + (dh,), jnp.int8),
            kb=jnp.zeros(shp, jnp.float32),
            ks=jnp.ones(shp, jnp.float32),
            vd=jnp.zeros(shp + (dh,), jnp.int8),
            vb=jnp.zeros(shp, jnp.float32),
            vs=jnp.ones(shp, jnp.float32),
        )

    def compress_kv_pages(self, k, v):
        return ref.compress_kv_pages(k, v)

    def compress_kv_pages_fused(self, k, v):
        return ops.compress_kv_pages(k, v)     # bit-exact with the oracle

    def decompress_pages(self, pages):
        return (ref.dequant_pages(pages.kd, pages.kb, pages.ks),
                ref.dequant_pages(pages.vd, pages.vb, pages.vs))

    def page_nbytes(self, pages) -> jax.Array:
        def side(d, b):
            zero_row = jnp.all(d == 0, axis=-1) & (b == 0.0)  # [n, K, page]
            data = jnp.where(zero_row, 0, d.shape[-1])
            return (jnp.sum(data, axis=(1, 2))
                    + 8 * d.shape[1] * d.shape[2])
        return (side(pages.kd, pages.kb)
                + side(pages.vd, pages.vb)).astype(jnp.int32)

    def paged_attention_tail(self, q, pages, page_table, lengths,
                             tail_k, tail_v, tail_len):
        return paged_attention_tail(q, pages, page_table, lengths,
                                    tail_k, tail_v, tail_len)


BDI = register(BDICodec())
