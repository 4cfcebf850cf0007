"""GBDI page codec: multi-base B+Delta with per-row base id and width.

GBDI (arxiv 2501.14812) observes that a single first-value base loses on
mixed-content pages — a page whose rows cluster around several distinct
magnitudes (system-prompt tokens next to generated tokens, zero runs
next to dense values) forces one wide delta range.  Picking K bases per
page by value clustering and giving each row a 2-bit base id plus a
delta-width tag recovers the loss at ~2 bytes/row of metadata, versus
BDI's 8-byte base+scale pair per row.

The math lives in ``kernels/gbdi_codec.py`` (shared bit-exactly between
the jnp oracle and the Pallas compress/decompress pair registered
through ``kernels/ops.py``); this module adapts it to the
:class:`~repro.codecs.base.PageCodec` protocol.

Byte accounting per side: ``K_BASES * 4`` bytes of page bases + 2 bytes
of packed row metadata (base id, width tag, scale exponent) per row +
data bytes by width class (0 for zero-run rows, ceil(D/2) for 4-bit
rows, D for 8-bit rows).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import gbdi_codec, ops
from repro.kernels.gbdi_codec import GBDIKVPages, K_BASES

from .base import PageCodec, register


class GBDICodec(PageCodec):
    name = "gbdi"
    lossless = False               # int8/int4 quantization: |err| <= scale/2
    has_fused_kernels = False      # no fused attention kernel
    has_fused_fill = True          # Pallas compress/decompress pair

    def init_pools(self, n_pages, kvh, page, dh):
        shp = (n_pages, kvh, page)
        bshp = (n_pages, K_BASES)
        return GBDIKVPages(
            kd=jnp.zeros(shp + (dh,), jnp.int8),
            kbs=jnp.zeros(bshp, jnp.float32),
            kbid=jnp.zeros(shp, jnp.int8),
            ksc=jnp.ones(shp, jnp.float32),
            kwid=jnp.zeros(shp, jnp.int8),
            vd=jnp.zeros(shp + (dh,), jnp.int8),
            vbs=jnp.zeros(bshp, jnp.float32),
            vbid=jnp.zeros(shp, jnp.int8),
            vsc=jnp.ones(shp, jnp.float32),
            vwid=jnp.zeros(shp, jnp.int8),
        )

    def compress_kv_pages(self, k, v):
        n, kvh, page, dh = k.shape

        def enc(x):
            rows = x.astype(jnp.float32).reshape(n, kvh * page, dh)
            d, bs, bid, sc, wid = gbdi_codec.encode_pages_ref(rows)
            return (d.reshape(n, kvh, page, dh), bs,
                    bid.reshape(n, kvh, page), sc.reshape(n, kvh, page),
                    wid.reshape(n, kvh, page))

        kd, kbs, kbid, ksc, kwid = enc(k)
        vd, vbs, vbid, vsc, vwid = enc(v)
        return GBDIKVPages(kd, kbs, kbid, ksc, kwid,
                           vd, vbs, vbid, vsc, vwid)

    def compress_kv_pages_fused(self, k, v):
        return ops.gbdi_compress_kv_pages(k, v)  # bit-exact with the oracle

    def decompress_pages(self, pages):
        def dec(d, bases, bid, sc):
            base = jnp.zeros_like(sc)
            for j in range(K_BASES):
                base = jnp.where(bid == j, bases[..., j][..., None, None],
                                 base)
            return d.astype(jnp.float32) * sc[..., None] + base[..., None]

        return (dec(pages.kd, pages.kbs, pages.kbid, pages.ksc),
                dec(pages.vd, pages.vbs, pages.vbid, pages.vsc))

    def page_nbytes(self, pages) -> jax.Array:
        def side(wid, dh):
            rows = wid.shape[-2] * wid.shape[-1]
            data = jnp.where(wid == 0, 0,
                             jnp.where(wid == 1, (dh + 1) // 2, dh))
            return (jnp.sum(data, axis=(-2, -1))
                    + K_BASES * 4 + 2 * rows)
        return (side(pages.kwid, pages.kd.shape[-1])
                + side(pages.vwid, pages.vd.shape[-1])).astype(jnp.int32)


GBDI = register(GBDICodec())
