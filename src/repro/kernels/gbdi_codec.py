"""Pallas TPU kernels: GBDI (multi-base B+Delta) KV-page compression.

GBDI (arxiv 2501.14812) generalizes BDI's single first-value base to K
bases chosen per page by value clustering, with a per-row base id and a
per-row delta width.  This module implements the page-fill form used by
the serving engines:

  * K bases per page on a dyadic lattice spanning the page's anchor
    range (each row's anchor is its first element; fractions
    {0, .., 1/4, 1/2, 1} of the range).  A lattice is a sort-free 1-D
    clustering grid — deterministic, branch-free, and directly
    expressible in a Pallas kernel body; each row then binds to its
    nearest base (one k-means assignment step).  Dyadic fractions keep
    ``amin + span * frac`` exact under FMA contraction (see inline
    comment), which is what makes kernel-vs-oracle parity bit-exact.
  * Residuals against the chosen base quantize to int8 at a hybrid
    power-of-two scale: a shared page scale when the row's max residual
    fits 4 signed bits at that scale, else the row's own scale.  The
    per-row width tag records which (0 = all-zero deltas, 1 = 4-bit,
    2 = 8-bit) and drives the byte accounting.

The pow-of-two scale uses the exponent-bitcast of
``repro.core.bdi_value._pow2_scale`` so the kernel reproduces the jnp
oracle bit-exactly.  ``encode_pages_ref`` / ``decode_pages_ref`` are the
oracles: they vmap the *same* per-page function the kernel bodies call,
so kernel-vs-oracle parity is structural, not coincidental (pinned in
tests/test_codecs.py).

Why the hybrid scale matters: a per-row pow2 scale always normalizes the
row's max |delta| into (63.5, 127], so a 4-bit width would never fire.
Rows that are tight *relative to the page's dynamic range* keep the page
scale and drop to 4-bit deltas at the same absolute error as the page's
8-bit rows.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._backend import resolve_interpret

K_BASES = 4
_QMAX = 127.0
_Q4MAX = 7.0  # signed 4-bit delta range used by width class 1


class GBDIKVPages(NamedTuple):
    """Multi-base compressed KV pages (pool: leading [P]; fresh: [n]).

    Per side: int8 deltas [..., KVH, page, D], f32 bases [..., K_BASES],
    int8 base id [..., KVH, page], f32 scale [..., KVH, page], int8 width
    tag [..., KVH, page] (0 zero-run, 1 four-bit, 2 eight-bit).
    """

    kd: jax.Array
    kbs: jax.Array
    kbid: jax.Array
    ksc: jax.Array
    kwid: jax.Array
    vd: jax.Array
    vbs: jax.Array
    vbid: jax.Array
    vsc: jax.Array
    vwid: jax.Array


def _pow2_scale(maxres: jax.Array) -> jax.Array:
    """Smallest pow2 s with maxres/s <= 127, by exponent bitcast."""
    ratio = maxres / _QMAX
    bits = jax.lax.bitcast_convert_type(ratio, jnp.int32)
    e = ((bits >> 23) & 0xFF) - 127
    e = e + (bits & 0x7FFFFF != 0).astype(jnp.int32)
    s = jnp.exp2(e.astype(jnp.float32))
    return jnp.where(maxres > 0, s, jnp.float32(1.0))


def _encode_page(x: jax.Array):
    """One page's rows [R, D] f32 -> (d i8 [R, D], bases f32 [1, K],
    bid i8 [R, 1], sc f32 [R, 1], wid i8 [R, 1]).

    Shared by the Pallas kernel body (one grid step = one page) and the
    vmapped jnp oracle; every op is elementwise or an exact reduction
    (min/max/abs), so both paths produce identical bits.
    """
    anchors = x[:, 0:1]                                 # [R, 1]
    amin = jnp.min(anchors, axis=0, keepdims=True)      # [1, 1]
    amax = jnp.max(anchors, axis=0, keepdims=True)
    # dyadic lattice fractions {0, ..., 1/4, 1/2, 1}: span * frac is an
    # exact power-of-two scaling, so `amin + span * frac` rounds once
    # whether or not the compiler contracts it to an FMA — keeping the
    # kernel and the vmapped oracle bit-identical
    j = jax.lax.broadcasted_iota(jnp.int32, (1, K_BASES), 1)
    frac = jnp.where(j == 0, jnp.float32(0.0),
                     jnp.exp2((j - (K_BASES - 1)).astype(jnp.float32)))
    bases = amin + (amax - amin) * frac                 # [1, K]

    # nearest base per row: explicit first-min where-chain (NOT argmin)
    # so the kernel and the oracle share one deterministic tie-break
    dist = jnp.abs(anchors - bases)                     # [R, K]
    best = dist[:, 0:1]
    bid = jnp.zeros_like(best, dtype=jnp.int32)         # [R, 1]
    for j in range(1, K_BASES):
        better = dist[:, j:j + 1] < best
        bid = jnp.where(better, j, bid)
        best = jnp.where(better, dist[:, j:j + 1], best)
    base_row = jnp.zeros_like(best)
    for j in range(K_BASES):
        base_row = jnp.where(bid == j, bases[:, j:j + 1], base_row)

    r = x - base_row                                    # [R, D]
    maxr_row = jnp.max(jnp.abs(r), axis=1, keepdims=True)
    maxr_page = jnp.max(maxr_row, axis=0, keepdims=True)
    ps = _pow2_scale(maxr_page)                         # [1, 1]
    fits4 = maxr_row <= _Q4MAX * ps                     # page-scale 4-bit rows
    scale = jnp.where(fits4, ps, _pow2_scale(maxr_row))
    d = jnp.clip(jnp.round(r / scale), -_QMAX, _QMAX)

    maxd = jnp.max(jnp.abs(d), axis=1, keepdims=True)
    wid = jnp.where(maxd == 0, 0, jnp.where(fits4, 1, 2))
    return (d.astype(jnp.int8), bases, bid.astype(jnp.int8), scale,
            wid.astype(jnp.int8))


def _decode_page(d: jax.Array, bases: jax.Array, bid: jax.Array,
                 sc: jax.Array) -> jax.Array:
    """Inverse of :func:`_encode_page`: [R, D] f32 reconstruction."""
    base_row = jnp.zeros_like(sc)
    for j in range(K_BASES):
        base_row = jnp.where(bid == j, bases[:, j:j + 1], base_row)
    return d.astype(jnp.float32) * sc + base_row


def encode_pages_ref(x: jax.Array):
    """jnp oracle: rows [n, R, D] -> per-page encode outputs, bit-exact
    with the Pallas compress kernel (same :func:`_encode_page` body)."""
    d, bases, bid, sc, wid = jax.vmap(_encode_page)(x)
    return d, bases[:, 0], bid[:, :, 0], sc[:, :, 0], wid[:, :, 0]


def decode_pages_ref(d, bases, bid, sc) -> jax.Array:
    """jnp oracle for the decompress kernel: [n, R, D] reconstruction."""
    return jax.vmap(_decode_page)(d, bases[:, None, :], bid[:, :, None],
                                  sc[:, :, None])


# The kernels see the [pages, K] bases as [pages, 1, K]: one page's
# (1, K) block then spans the array's last two dims, which is the block
# shape the TPU compiler accepts for a K-wide minor dim.
_BASES_BLOCK = pl.BlockSpec((1, 1, K_BASES), lambda i: (i, 0, 0))


def _gbdi_compress_kernel(x_ref, d_ref, bases_ref, bid_ref, sc_ref, wid_ref):
    d, bases, bid, sc, wid = _encode_page(x_ref[...].astype(jnp.float32))
    d_ref[...] = d
    bases_ref[0] = bases
    bid_ref[...] = bid
    sc_ref[...] = sc
    wid_ref[...] = wid


def _gbdi_decompress_kernel(d_ref, bases_ref, bid_ref, sc_ref, out_ref):
    out_ref[...] = _decode_page(d_ref[...], bases_ref[0],
                                bid_ref[...].astype(jnp.int32), sc_ref[...])


def gbdi_compress(x: jax.Array, *, rows_per_page: int,
                  interpret: bool | None = None):
    """x f32 [n_pages * rows_per_page, D] -> (d i8, bases f32 [n, K],
    bid i8 [N, 1], sc f32 [N, 1], wid i8 [N, 1]); one grid step per page.

    ``interpret=None`` resolves from the backend.
    """
    d, bases, bid, sc, wid = _gbdi_compress(
        x, rows_per_page=rows_per_page,
        interpret=resolve_interpret(interpret))
    return d, bases[:, 0], bid, sc, wid


@functools.partial(jax.jit, static_argnames=("rows_per_page", "interpret"))
def _gbdi_compress(x: jax.Array, *, rows_per_page: int, interpret: bool):
    n, d = x.shape
    assert n % rows_per_page == 0, (n, rows_per_page)
    pages = n // rows_per_page
    grid = (pages,)
    row = lambda i: (i, 0)  # noqa: E731
    return pl.pallas_call(
        _gbdi_compress_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows_per_page, d), row)],
        out_specs=[
            pl.BlockSpec((rows_per_page, d), row),
            _BASES_BLOCK,
            pl.BlockSpec((rows_per_page, 1), row),
            pl.BlockSpec((rows_per_page, 1), row),
            pl.BlockSpec((rows_per_page, 1), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), jnp.int8),
            jax.ShapeDtypeStruct((pages, 1, K_BASES), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int8),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.int8),
        ],
        interpret=interpret,
    )(x)


def gbdi_decompress(d: jax.Array, bases: jax.Array, bid: jax.Array,
                    sc: jax.Array, *, rows_per_page: int,
                    interpret: bool | None = None) -> jax.Array:
    """(d i8 [N, D], bases f32 [n, K], bid i8 [N, 1], sc f32 [N, 1]) ->
    f32 [N, D] rows, pairing :func:`gbdi_compress`."""
    return _gbdi_decompress(d, bases[:, None, :], bid, sc,
                            rows_per_page=rows_per_page,
                            interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("rows_per_page", "interpret"))
def _gbdi_decompress(d: jax.Array, bases: jax.Array, bid: jax.Array,
                     sc: jax.Array, *, rows_per_page: int, interpret: bool):
    n, dd = d.shape
    assert n % rows_per_page == 0, (n, rows_per_page)
    pages = n // rows_per_page
    grid = (pages,)
    row = lambda i: (i, 0)  # noqa: E731
    return pl.pallas_call(
        _gbdi_decompress_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows_per_page, dd), row),
            _BASES_BLOCK,
            pl.BlockSpec((rows_per_page, 1), row),
            pl.BlockSpec((rows_per_page, 1), row),
        ],
        out_specs=[pl.BlockSpec((rows_per_page, dd), row)],
        out_shape=[jax.ShapeDtypeStruct((n, dd), jnp.float32)],
        interpret=interpret,
    )(d, bases, bid, sc)[0]
