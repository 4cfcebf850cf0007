"""Pallas TPU kernel: BDI tile compression.

Implements the two-step BDI algorithm of Section 3.5.1 in value space:

  Step 1 (immediate): residual against the implicit zero base.
  Step 2 (base):      residual against the tile's first value.
  Per element, the nearer base wins (the paper's zero-base bitmask).

The power-of-two shared scale is derived from the max |residual| by exponent
bitcast (identical to ``repro.core.bdi_value._pow2_scale``), then deltas are
rounded to int8. Outputs match ``kernels.ref.compress_ref`` bit-exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bdi_value import ENC_D8, ENC_REP, ENC_ZERO

from ._backend import resolve_interpret

_QMAX = 127.0


def _compress_kernel(x_ref, deltas_ref, base_ref, scale_ref, maskp_ref,
                     enc_ref):
    bn, t = x_ref.shape
    w = t // 8
    x = x_ref[...].astype(jnp.float32)

    base = x[:, 0:1]                                   # first-value base
    r_zero = x
    r_base = x - base
    mask = jnp.abs(r_base) < jnp.abs(r_zero)           # nearer base wins
    r = jnp.where(mask, r_base, r_zero)

    maxres = jnp.max(jnp.abs(r), axis=1, keepdims=True)
    ratio = maxres / _QMAX
    bits = jax.lax.bitcast_convert_type(ratio, jnp.int32)
    e = ((bits >> 23) & 0xFF) - 127
    e = e + (bits & 0x7FFFFF != 0).astype(jnp.int32)
    scale = jnp.exp2(e.astype(jnp.float32))
    scale = jnp.where(maxres > 0, scale, jnp.float32(1.0))

    deltas = jnp.clip(jnp.round(r / scale), -_QMAX, _QMAX)

    maxabs = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    is_zero = maxabs == 0.0
    is_rep = jnp.all(x == base, axis=1, keepdims=True) & ~is_zero
    enc = jnp.where(is_rep, ENC_REP, ENC_D8)
    enc = jnp.where(is_zero, ENC_ZERO, enc)

    simple = is_zero | is_rep
    deltas = jnp.where(simple, 0.0, deltas)
    mask = jnp.where(is_zero, False, jnp.where(is_rep, True, mask))
    base = jnp.where(is_zero, 0.0, base)

    # Bit-plane pack: element j -> byte j % w, bit j // w.
    mi = mask.astype(jnp.int32)
    packed = jnp.zeros((bn, w), jnp.int32)
    for bit in range(8):
        packed = packed | (mi[:, bit * w:(bit + 1) * w] << bit)

    deltas_ref[...] = deltas.astype(jnp.int8)
    base_ref[...] = base
    scale_ref[...] = scale
    maskp_ref[...] = packed.astype(jnp.uint8)
    enc_ref[...] = enc.astype(jnp.int32)


def _compress_kv_kernel(x_ref, deltas_ref, base_ref, scale_ref):
    """Single-base row codec: the KV page-fill form of BDI.

    One row = one (head, token) vector of a KV page.  Base is the row's
    first element, scale the power-of-two derived from max |residual| —
    identical math to :func:`ref.compress_kv_pages` (and to the Step-2
    branch of the tile kernel above), so outputs are bit-exact with the
    jnp oracle.  No zero-base mask: KV value distributions never win it
    (measured in benchmarks/bench_lcp.py).
    """
    x = x_ref[...].astype(jnp.float32)                 # [bn, d]
    base = x[:, 0:1]
    r = x - base
    maxres = jnp.max(jnp.abs(r), axis=1, keepdims=True)
    ratio = maxres / _QMAX
    bits = jax.lax.bitcast_convert_type(ratio, jnp.int32)
    e = ((bits >> 23) & 0xFF) - 127
    e = e + (bits & 0x7FFFFF != 0).astype(jnp.int32)
    scale = jnp.exp2(e.astype(jnp.float32))
    scale = jnp.where(maxres > 0, scale, jnp.float32(1.0))
    deltas = jnp.clip(jnp.round(r / scale), -_QMAX, _QMAX)

    deltas_ref[...] = deltas.astype(jnp.int8)
    base_ref[...] = base
    scale_ref[...] = scale


def bdi_compress_kv(x: jax.Array, *, block_n: int = 8,
                    interpret: bool | None = None):
    """x f32 [N, D] rows -> (deltas i8 [N, D], base f32 [N, 1], scale f32
    [N, 1]): the batched page-fill entry point for the serving engines.

    ``interpret=None`` resolves from the backend.  D is the head dim
    (typically 64/128); on TPU lanes pad to 128, which is fine for a
    fill-path kernel that runs off the decode critical path.
    """
    return _bdi_compress_kv(x, block_n=block_n,
                            interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _bdi_compress_kv(x: jax.Array, *, block_n: int, interpret: bool):
    n, d = x.shape
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    row = lambda i: (i, 0)  # noqa: E731
    return pl.pallas_call(
        _compress_kv_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, d), row)],
        out_specs=[
            pl.BlockSpec((block_n, d), row),
            pl.BlockSpec((block_n, 1), row),
            pl.BlockSpec((block_n, 1), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, d), jnp.int8),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)


def bdi_compress(x: jax.Array, *, block_n: int = 8,
                 interpret: bool | None = None):
    """x f32 [N, T] -> (deltas i8, base f32, scale f32, maskp u8, enc i32).

    ``interpret=None`` resolves from the backend (compiled on TPU,
    interpret elsewhere).
    """
    return _bdi_compress(x, block_n=block_n,
                         interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _bdi_compress(x: jax.Array, *, block_n: int, interpret: bool):
    n, t = x.shape
    assert n % block_n == 0 and t % 8 == 0, (n, t, block_n)
    grid = (n // block_n,)
    row = lambda i: (i, 0)  # noqa: E731
    return pl.pallas_call(
        _compress_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_n, t), row)],
        out_specs=[
            pl.BlockSpec((block_n, t), row),
            pl.BlockSpec((block_n, 1), row),
            pl.BlockSpec((block_n, 1), row),
            pl.BlockSpec((block_n, t // 8), row),
            pl.BlockSpec((block_n, 1), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, t), jnp.int8),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, t // 8), jnp.uint8),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
        ],
        interpret=interpret,
    )(x)
