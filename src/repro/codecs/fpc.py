"""FPC page codec: frequent-pattern coding over fp32 words, lossless.

FPC-style word coding (Burtscher & Ratanaworabhan's frequent-pattern
idea, applied LCP-style to KV pages): every f32 word gets a 2-bit
prefix class picked from the page's frequent patterns, with *exact*
exception payloads for words that match no pattern:

  class 0  +0.0 word                 (prefix only)
  class 1  bit-exact repeat of the previous word along D (prefix only)
  class 2  bf16-exact word           (prefix + top 16 bits)
  class 3  exception                 (prefix + full 32-bit payload)

Classification is on the raw bit pattern, so the codec is lossless
bit-for-bit: -0.0 is not class 0 (it round-trips through class 2's
``0x8000`` top half), repeats are bit-equality chains, and exceptions
carry the untouched word.  ``lossless = True`` lets the engines skip
the canonical roundtrip in prefill (same contract as the raw codec).

Storage is class-planar (a class plane + masked payload planes) rather
than a packed byte stream — pool leaves must be fixed-shape device
arrays — but ``page_nbytes`` accounts the *packed* size: 2 bits of
prefix per word plus 16/32 payload bits for classes 2/3, matching what
a memory-hierarchy FPC line would spend.

Honest expectations: dense f32 KV content costs ~4.25 bytes/word (every
word an exception), worse than raw's 2-byte bf16 accounting — FPC wins
on zero runs, repeated rows, and bf16-exact values.  Under the
``adaptive`` composite that is exactly its niche; it never needs to win
dense pages.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .base import PageCodec, register


class FPCKVPages(NamedTuple):
    """Class-planar FPC pages (pool: leading [P]; fresh: [n]).

    Per side: 2-bit class plane (u8) [..., KVH, page, D], class-2 top
    halves (u16, zero elsewhere), class-3 exception payloads (f32, zero
    elsewhere).  Distinct buffers per field: the engines donate the
    pool pytree into the publish dispatch.
    """

    kcls: jax.Array
    khi: jax.Array
    kexc: jax.Array
    vcls: jax.Array
    vhi: jax.Array
    vexc: jax.Array


def _encode_side(x: jax.Array):
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    is_zero = bits == 0                                  # +0.0 exactly
    rep_tail = bits[..., 1:] == bits[..., :-1]           # bit-equal repeat
    is_rep = jnp.concatenate(
        [jnp.zeros_like(is_zero[..., :1]), rep_tail], axis=-1)
    is_bf16 = (bits & 0xFFFF) == 0                       # bf16-exact word
    cls = jnp.where(is_zero, 0,
                    jnp.where(is_rep, 1,
                              jnp.where(is_bf16, 2, 3))).astype(jnp.uint8)
    hi = jnp.where(cls == 2, (bits >> 16).astype(jnp.uint16),
                   jnp.uint16(0))
    exc = jnp.where(cls == 3, x.astype(jnp.float32), jnp.float32(0.0))
    return cls, hi, exc


def _decode_side(cls: jax.Array, hi: jax.Array, exc: jax.Array) -> jax.Array:
    bfval = jax.lax.bitcast_convert_type(
        hi.astype(jnp.uint32) << 16, jnp.float32)
    explicit = jnp.where(cls == 0, jnp.float32(0.0),
                         jnp.where(cls == 2, bfval, exc))
    # repeat chains carry the nearest explicit word forward along D:
    # cummax over explicit positions, then gather.  Position 0 is never
    # class 1, so every repeat has an explicit source to its left.
    axis = cls.ndim - 1
    idx = jax.lax.broadcasted_iota(jnp.int32, cls.shape, axis)
    src = jax.lax.cummax(jnp.where(cls == 1, -1, idx), axis=axis)
    return jnp.take_along_axis(explicit, src, axis=-1)


class FPCCodec(PageCodec):
    name = "fpc"
    lossless = True                # bit-pattern coding, exact exceptions
    ulp_stable_sizes = False       # sizes read exact mantissa bits
    has_fused_kernels = False

    def init_pools(self, n_pages, kvh, page, dh):
        shp = (n_pages, kvh, page, dh)
        return FPCKVPages(
            kcls=jnp.zeros(shp, jnp.uint8),
            khi=jnp.zeros(shp, jnp.uint16),
            kexc=jnp.zeros(shp, jnp.float32),
            vcls=jnp.zeros(shp, jnp.uint8),
            vhi=jnp.zeros(shp, jnp.uint16),
            vexc=jnp.zeros(shp, jnp.float32),
        )

    def compress_kv_pages(self, k, v):
        kcls, khi, kexc = _encode_side(k)
        vcls, vhi, vexc = _encode_side(v)
        return FPCKVPages(kcls, khi, kexc, vcls, vhi, vexc)

    def decompress_pages(self, pages):
        return (_decode_side(pages.kcls, pages.khi, pages.kexc),
                _decode_side(pages.vcls, pages.vhi, pages.vexc))

    def page_nbytes(self, pages) -> jax.Array:
        def side(cls):
            words = cls.shape[-3] * cls.shape[-2] * cls.shape[-1]
            pay = jnp.where(cls == 2, 16, jnp.where(cls == 3, 32, 0))
            bits = jnp.sum(pay, axis=(-3, -2, -1)) + 2 * words
            return (bits + 7) // 8
        return (side(pages.kcls) + side(pages.vcls)).astype(jnp.int32)


FPC = register(FPCCodec())
