"""Public jit'd wrappers for the Pallas kernels.

Handle padding to block multiples, dtype/layout adaptation, and backend
dispatch: on TPU the Pallas path compiles natively; elsewhere kernels run in
``interpret=True`` mode (the kernel body executed on CPU for validation).
The policy lives in :func:`default_interpret` (re-exported from
``kernels._backend``): False on TPU backends, True otherwise.

This module is the **BDI instance's** kernel surface: the serving stack
never imports it directly anymore — it consumes the
:class:`repro.codecs.PageCodec` protocol, and ``codecs/bdi.py`` adapts
these entry points to it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import bdi_value as bv

from . import gbdi_codec, ref
from ._backend import default_interpret, resolve_interpret  # noqa: F401
from .bdi_compress import bdi_compress as _compress_kernel
from .bdi_compress import bdi_compress_kv as _compress_kv_kernel
from .bdi_decompress import bdi_decompress as _decompress_kernel
from .paged_attention import paged_attention as _paged_attention_kernel
from .paged_attention import paged_attention_tail as _paged_attention_tail


def _pad_rows(x: jax.Array, mult: int) -> tuple[jax.Array, int]:
    n = x.shape[0]
    pad = (-n) % mult
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], axis=0)
    return x, n


def compress(x: jax.Array, *, block_n: int = 8) -> ref.PackedTiles:
    """Compress f32 tiles [N, T] with the Pallas compressor."""
    xp, n = _pad_rows(x.astype(jnp.float32), block_n)
    deltas, base, scale, maskp, enc = _compress_kernel(xp, block_n=block_n)
    return ref.PackedTiles(deltas[:n], base[:n], scale[:n], maskp[:n], enc[:n])


def decompress(p: ref.PackedTiles, *, block_n: int = 8) -> jax.Array:
    """Decompress PackedTiles to f32 [N, T] with the Pallas decompressor.

    No scale patch-up here: the compressors guarantee a valid scale for
    every tile — all-constant (incl. all-zero) tiles have zero max
    residual and emit scale 1.0 (``_pow2_scale``'s ``maxres > 0`` guard,
    reproduced bit-exactly in the Pallas kernel); pad rows appended
    below are sliced off before anything reads them.  Pinned by the
    all-zeros/all-constant roundtrip tests in tests/test_kernels.py.
    """
    n = p.deltas.shape[0]
    deltas, _ = _pad_rows(p.deltas, block_n)
    base, _ = _pad_rows(p.base, block_n)
    scale, _ = _pad_rows(p.scale, block_n)
    maskp, _ = _pad_rows(p.maskp, block_n)
    return _decompress_kernel(deltas, base, scale, maskp,
                              block_n=block_n)[:n]


def compress_kv_pages(k: jax.Array, v: jax.Array, *,
                      interpret: bool | None = None,
                      block_n: int = 8) -> ref.CompressedKVPages:
    """Batched KV page-fill through the Pallas row codec.

    k, v: f32 [P, KVH, page, D] -> single-base compressed pages, bit-exact
    with :func:`ref.compress_kv_pages`.  This is the chunked-prefill /
    decode page-publish entry point: every freshly filled page of every
    layer compresses in one kernel dispatch.
    """
    p, kvh, page, d = k.shape

    def enc(x):
        rows, n = _pad_rows(x.astype(jnp.float32).reshape(-1, d), block_n)
        deltas, base, scale = _compress_kv_kernel(rows, block_n=block_n,
                                                  interpret=interpret)
        return (deltas[:n].reshape(p, kvh, page, d),
                base[:n, 0].reshape(p, kvh, page),
                scale[:n, 0].reshape(p, kvh, page))

    kd, kb, ks = enc(k)
    vd, vb, vs = enc(v)
    return ref.CompressedKVPages(kd, kb, ks, vd, vb, vs)


def gbdi_compress_kv_pages(k: jax.Array, v: jax.Array, *,
                           interpret: bool | None = None
                           ) -> gbdi_codec.GBDIKVPages:
    """Batched KV page-fill through the Pallas GBDI (multi-base) codec.

    k, v: f32 [P, KVH, page, D] -> multi-base compressed pages, bit-exact
    with the ``gbdi_codec.encode_pages_ref`` oracle (the codec's
    reference ``compress_kv_pages`` path).  One kernel grid step per
    page; no row padding needed because blocks are page-granular.
    """
    p, kvh, page, d = k.shape
    rows_per_page = kvh * page

    def enc(x):
        rows = x.astype(jnp.float32).reshape(-1, d)
        dd, bs, bid, sc, wid = gbdi_codec.gbdi_compress(
            rows, rows_per_page=rows_per_page, interpret=interpret)
        return (dd.reshape(p, kvh, page, d), bs,
                bid[:, 0].reshape(p, kvh, page),
                sc[:, 0].reshape(p, kvh, page),
                wid[:, 0].reshape(p, kvh, page))

    kd, kbs, kbid, ksc, kwid = enc(k)
    vd, vbs, vbid, vsc, vwid = enc(v)
    return gbdi_codec.GBDIKVPages(kd, kbs, kbid, ksc, kwid,
                                  vd, vbs, vbid, vsc, vwid)


def gbdi_decompress_kv_pages(pages: gbdi_codec.GBDIKVPages, *,
                             interpret: bool | None = None
                             ) -> tuple[jax.Array, jax.Array]:
    """Decompress GBDI pages [P, ...] back to f32 K/V [P, KVH, page, D]
    through the Pallas decompressor (pairs gbdi_compress_kv_pages)."""
    p, kvh, page, d = pages.kd.shape
    rows_per_page = kvh * page

    def dec(dd, bs, bid, sc):
        out = gbdi_codec.gbdi_decompress(
            dd.reshape(-1, d), bs, bid.reshape(-1, 1), sc.reshape(-1, 1),
            rows_per_page=rows_per_page, interpret=interpret)
        return out.reshape(p, kvh, page, d)

    k = dec(pages.kd, pages.kbs, pages.kbid, pages.ksc)
    v = dec(pages.vd, pages.vbs, pages.vbid, pages.vsc)
    return k, v


def paged_attention(q: jax.Array, pages: ref.CompressedKVPages,
                    page_table: jax.Array, lengths: jax.Array) -> jax.Array:
    """Fused compressed-paged-KV decode attention (see paged_attention.py)."""
    return _paged_attention_kernel(q, pages, page_table, lengths)


def paged_attention_tail(q: jax.Array, pages: ref.CompressedKVPages,
                         page_table: jax.Array, lengths: jax.Array,
                         tail_k: jax.Array, tail_v: jax.Array,
                         tail_len: jax.Array) -> jax.Array:
    """Fused decode attention over [compressed pages + uncompressed tail]."""
    return _paged_attention_tail(q, pages, page_table, lengths,
                                 tail_k, tail_v, tail_len)


def roundtrip_tensor(x: jax.Array, tile: int = 128) -> jax.Array:
    """compress->decompress an arbitrary tensor through the kernels."""
    tiles, n = bv.fold_to_tiles(x, tile)
    out = decompress(compress(tiles))
    return bv.unfold_from_tiles(out, n, x.shape).astype(x.dtype)
