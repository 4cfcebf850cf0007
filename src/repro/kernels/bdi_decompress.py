"""Pallas TPU kernel: BDI tile decompression (the paper's masked vector add).

Decompresses int8 base+delta+immediate tiles to f32:

    out[n, t] = delta[n, t] * scale[n] + mask[n, t] * base[n]

— one fused multiply-add over a VREG tile, the direct TPU analogue of the
thesis' "masked SIMD addition" decompressor (Figure 3.10).

The zero-base bitmask arrives bit-plane packed (uint8 [N, T//8], see
kernels/ref.py) and is unpacked in-register with a lane-tile repeat plus a
constant per-group shift — no lane-crossing reshape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._backend import resolve_interpret


def _decompress_kernel(deltas_ref, base_ref, scale_ref, maskp_ref, out_ref):
    bn, t = deltas_ref.shape
    w = t // 8
    d = deltas_ref[...].astype(jnp.float32)
    b = base_ref[...].astype(jnp.float32)          # [bn, 1]
    s = scale_ref[...].astype(jnp.float32)         # [bn, 1]
    mp = maskp_ref[...].astype(jnp.int32)          # [bn, w]

    # Bit-plane unpack: position j holds byte j % w; its bit index is j // w.
    rep = jnp.concatenate([mp] * 8, axis=1)        # [bn, t]
    bit_idx = jax.lax.broadcasted_iota(jnp.int32, (bn, t), 1) // w
    mask = ((rep >> bit_idx) & 1).astype(jnp.float32)

    out_ref[...] = d * s + mask * b                # THE masked vector FMA


def bdi_decompress(deltas: jax.Array, base: jax.Array, scale: jax.Array,
                   maskp: jax.Array, *, block_n: int = 8,
                   interpret: bool | None = None) -> jax.Array:
    """deltas int8 [N, T], base/scale f32 [N, 1], maskp uint8 [N, T//8].

    ``interpret=None`` resolves from the backend (compiled on TPU,
    interpret elsewhere).
    """
    return _bdi_decompress(deltas, base, scale, maskp, block_n=block_n,
                           interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _bdi_decompress(deltas: jax.Array, base: jax.Array, scale: jax.Array,
                    maskp: jax.Array, *, block_n: int,
                    interpret: bool) -> jax.Array:
    n, t = deltas.shape
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    return pl.pallas_call(
        _decompress_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, t), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, t // 8), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, t), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, t), jnp.float32),
        interpret=interpret,
    )(deltas, base, scale, maskp)
