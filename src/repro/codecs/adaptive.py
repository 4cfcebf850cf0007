"""Adaptive page codec: per-page selection over every single codec.

The LCP framework stores cheap per-page metadata picking the best
encoding for each page; Touché (arxiv 1909.00553) shows the tag can be
a few bits co-located with the page-table entry rather than a separate
metadata walk.  This composite realizes both: the publish path
compresses each fresh page under every member codec, keeps the smallest
by the device-reported ``page_nbytes``, and stores the winning member
id as a one-byte tag leaf — the *first* leaf of the pool pytree, so it
rides the existing page-table gathers, the checksum walk in
``serving/faults.py`` (a flipped tag is detected like any flipped
payload bit), and the snapshot array dump for free.

Member order is part of the on-disk format (tags persist in snapshots
and prefix-cache state): ``bdi=0, zero=1, raw=2, gbdi=3, fpc=4``; ties
break to the lowest id.  Storage keeps every member's encoding of every
page (pool leaves must be fixed-shape device arrays — the class-planar
trade also made by the fpc codec); the byte *accounting* is the winner's
packed size plus the one-byte tag, which is what CAMP preemption values
and SIP retention ranking consume.

All selection happens on-device inside the publish dispatch: admit and
retire never retrace, and the tag travels as just another pool leaf
through ``_mixed_step``'s ``lax.scan``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .base import PageCodec, register
from .bdi import BDI
from .fpc import FPC
from .gbdi import GBDI
from .raw import RAW
from .zero import ZERO

#: member order is persisted (snapshot arrays, prefix-cache codec_ids)
MEMBER_NAMES = ("bdi", "zero", "raw", "gbdi", "fpc")
MEMBERS = (BDI, ZERO, RAW, GBDI, FPC)
TAG_NBYTES = 1


class AdaptiveKVPages(NamedTuple):
    """Tag leaf + one member pytree per codec.  ``tag`` MUST stay the
    first field: ``faults.corrupt_page`` flips a bit in the first
    nonempty leaf, so chaos corruption exercises tag recovery, and the
    snapshot dump's ``pool_000`` is the tag plane."""

    tag: jax.Array      # uint8 [..., ] winning member id per page
    bdi: NamedTuple
    zero: NamedTuple
    raw: NamedTuple
    gbdi: NamedTuple
    fpc: NamedTuple


class AdaptiveCodec(PageCodec):
    name = "adaptive"
    lossless = False               # lossy members can win pages
    ulp_stable_sizes = False       # min() over members includes fpc
    has_fused_kernels = False      # members' attention kernels not shared
    has_fused_fill = True          # members' fused fill paths compose

    members = MEMBERS
    member_names = MEMBER_NAMES

    def init_pools(self, n_pages, kvh, page, dh):
        return AdaptiveKVPages(
            jnp.zeros((n_pages,), jnp.uint8),
            *(m.init_pools(n_pages, kvh, page, dh)
              for m in self.members))

    def _compress(self, k, v, fused: bool):
        cands = tuple(
            (m.compress_kv_pages_fused(k, v) if fused
             else m.compress_kv_pages(k, v)) for m in self.members)
        sizes = [m.page_nbytes(c) for m, c in zip(self.members, cands)]
        # first-smallest wins: explicit where-chain, deterministic ties
        best = sizes[0]
        tag = jnp.zeros_like(best)
        for j in range(1, len(sizes)):
            better = sizes[j] < best
            tag = jnp.where(better, j, tag)
            best = jnp.where(better, sizes[j], best)
        return AdaptiveKVPages(tag.astype(jnp.uint8), *cands)

    def compress_kv_pages(self, k, v):
        return self._compress(k, v, fused=False)

    def compress_kv_pages_fused(self, k, v):
        # members' fused paths are bit-exact with their reference paths,
        # so sizes — and therefore tags — match the reference compress
        return self._compress(k, v, fused=True)

    def _member_pages(self, pages):
        return (pages.bdi, pages.zero, pages.raw, pages.gbdi, pages.fpc)

    def decompress_pages(self, pages):
        outs = [m.decompress_pages(c)
                for m, c in zip(self.members, self._member_pages(pages))]
        t = pages.tag.astype(jnp.int32)[..., None, None, None]
        k, v = outs[0]
        for j in range(1, len(outs)):
            k = jnp.where(t == j, outs[j][0], k)
            v = jnp.where(t == j, outs[j][1], v)
        return k, v

    def page_nbytes(self, pages) -> jax.Array:
        sizes = [m.page_nbytes(c)
                 for m, c in zip(self.members, self._member_pages(pages))]
        t = pages.tag.astype(jnp.int32)
        out = sizes[0]
        for j in range(1, len(sizes)):
            out = jnp.where(t == j, sizes[j], out)
        return (out + TAG_NBYTES).astype(jnp.int32)

    def page_tags(self, pages) -> jax.Array:
        return pages.tag.astype(jnp.int32)


ADAPTIVE = register(AdaptiveCodec())
