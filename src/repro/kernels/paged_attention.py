"""Pallas TPU kernel: paged decode attention fused with BDI-KV dequant.

This is the flagship kernel: the LCP-style compressed KV page pool
(int8 deltas + per-(token, head) base/scale — see DESIGN.md §2.2) is read
*directly* in its compressed form; dequantization fuses into the
flash-decoding inner loop, so HBM traffic for K/V is ~the int8 bytes.
This realizes the thesis' §5.5.1 "bandwidth reduction" optimization where it
matters on TPU: decode attention is HBM-bandwidth-bound.

Pattern: scalar-prefetched page table drives the BlockSpec index maps (the
LCP address computation — page_table[b, p] is the whole "locate compressed
data" story, one lookup + shift), online-softmax accumulation in VMEM
scratch across the page grid axis.

Two entry points:

  * ``paged_attention``       — compressed pages only (the original form);
  * ``paged_attention_tail``  — compressed pages **plus** one uncompressed
    f32 tail block per sequence (the serving engine's write buffer), fused
    as a final grid step so decode attention over [pages + tail] is a
    single kernel launch.  This is what ``serving/engine.py`` runs on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._backend import resolve_interpret
from .ref import CompressedKVPages


def _accumulate(q, k, v, valid, acc_ref, m_ref, l_ref):
    """One online-softmax block update; robust to fully-masked blocks."""
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    scores = jnp.where(valid, scores, -jnp.inf)

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    # A block may carry zero valid tokens (e.g. padded page table before the
    # first page is published): keep the running max at -inf without letting
    # exp(-inf - -inf) produce NaNs.
    m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
    alpha = jnp.where(m_prev == -jnp.inf, 0.0, jnp.exp(m_prev - m_safe))
    pij = jnp.where(scores == -jnp.inf, 0.0, jnp.exp(scores - m_safe))
    l_new = l_prev * alpha + jnp.sum(pij, axis=1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * alpha
                    + jax.lax.dot_general(pij, v, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _dequant_block(d_ref, b_ref, s_ref):
    return d_ref[0, 0].astype(jnp.float32) * s_ref[0, 0] + b_ref[0, 0]


def _paged_attn_kernel(pt_ref, len_ref,            # scalar prefetch
                       q_ref, kd_ref, kb_ref, ks_ref,
                       vd_ref, vb_ref, vs_ref,
                       out_ref,
                       acc_ref, m_ref, l_ref):
    b = pl.program_id(0)
    p = pl.program_id(2)
    n_pages = pl.num_programs(2)

    d = q_ref.shape[3]
    page = kd_ref.shape[2]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0] * jax.lax.rsqrt(jnp.float32(d))          # [g, d]
    k = _dequant_block(kd_ref, kb_ref, ks_ref)               # [page, d]
    v = _dequant_block(vd_ref, vb_ref, vs_ref)

    g = q_ref.shape[2]
    pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (g, page), 1)
    _accumulate(q, k, v, pos < len_ref[b], acc_ref, m_ref, l_ref)

    @pl.when(p == n_pages - 1)
    def _finalize():
        out_ref[0, 0] = acc_ref[...] / l_ref[:, :1]


def _paged_attn_tail_kernel(pt_ref, len_ref, tlen_ref,     # scalar prefetch
                            q_ref, kd_ref, kb_ref, ks_ref,
                            vd_ref, vb_ref, vs_ref,
                            tk_ref, tv_ref,
                            out_ref,
                            acc_ref, m_ref, l_ref):
    b = pl.program_id(0)
    p = pl.program_id(2)
    n_prog = pl.num_programs(2)                    # pmax page steps + 1 tail

    d = q_ref.shape[3]
    page = kd_ref.shape[2]
    g = q_ref.shape[2]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0] * jax.lax.rsqrt(jnp.float32(d))          # [g, d]

    @pl.when(p < n_prog - 1)
    def _pages():
        k = _dequant_block(kd_ref, kb_ref, ks_ref)
        v = _dequant_block(vd_ref, vb_ref, vs_ref)
        pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (g, page), 1)
        _accumulate(q, k, v, pos < len_ref[b], acc_ref, m_ref, l_ref)

    @pl.when(p == n_prog - 1)
    def _tail():
        k = tk_ref[0, 0].astype(jnp.float32)                 # [page, d]
        v = tv_ref[0, 0].astype(jnp.float32)
        slot = jax.lax.broadcasted_iota(jnp.int32, (g, page), 1)
        _accumulate(q, k, v, slot < tlen_ref[b], acc_ref, m_ref, l_ref)
        out_ref[0, 0] = acc_ref[...] / l_ref[:, :1]


def _table_scales(pages: CompressedKVPages, page_table: jax.Array):
    """Base/scale of the pages the table names, [B * PMAX, KVH, page, 1].

    The trailing singleton lets the kernel see [page, 1] tiles that
    broadcast against [page, d] without relayout.  It pads to a full
    lane tile on TPU, so the scales are gathered first: expanded in
    place, a pool's scales would take 128x their size.  Block (b, p) of
    the result is page_table[b, p]'s scales (see ``_scale_map``).
    """
    def gather(x):
        return x[page_table].reshape((-1,) + x.shape[1:])[..., None]
    return (gather(pages.kb), gather(pages.ks),
            gather(pages.vb), gather(pages.vs))


def _scale_map(pmax: int):
    """Index map of the gathered scales: row b * PMAX + p; the tail grid
    step (p == PMAX) clamps to the row's last page, which it never reads."""
    def index(b_i, h_i, p_i, *_):
        return (b_i * pmax + jnp.minimum(p_i, pmax - 1), h_i, 0, 0)
    return index


def paged_attention(q: jax.Array, pages: CompressedKVPages,
                    page_table: jax.Array, lengths: jax.Array,
                    *, interpret: bool | None = None) -> jax.Array:
    """q f32 [B, KVH, G, D]; page_table i32 [B, PMAX]; lengths i32 [B].

    ``interpret=None`` resolves from the backend (compiled on TPU,
    interpret elsewhere).
    """
    return _paged_attention(q, pages, page_table, lengths,
                            interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_attention(q, pages, page_table, lengths, *, interpret):
    bsz, kvh, g, d = q.shape
    pmax = page_table.shape[1]
    page = pages.kd.shape[2]
    kb, ks, vb, vs = _table_scales(pages, page_table)
    sc_map = _scale_map(pmax)

    def kv_map(b_i, h_i, p_i, pt, ln):
        del ln
        return (pt[b_i, p_i], h_i, 0, 0)

    def q_map(b_i, h_i, p_i, pt, ln):
        del p_i, pt, ln
        return (b_i, h_i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, kvh, pmax),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), q_map),
            pl.BlockSpec((1, 1, page, d), kv_map),
            pl.BlockSpec((1, 1, page, 1), sc_map),
            pl.BlockSpec((1, 1, page, 1), sc_map),
            pl.BlockSpec((1, 1, page, d), kv_map),
            pl.BlockSpec((1, 1, page, 1), sc_map),
            pl.BlockSpec((1, 1, page, 1), sc_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        _paged_attn_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, kvh, g, d), jnp.float32),
        interpret=interpret,
    )(page_table, lengths, q, pages.kd, kb, ks, pages.vd, vb, vs)


def paged_attention_tail(q: jax.Array, pages: CompressedKVPages,
                         page_table: jax.Array, lengths: jax.Array,
                         tail_k: jax.Array, tail_v: jax.Array,
                         tail_len: jax.Array,
                         *, interpret: bool | None = None) -> jax.Array:
    """Decode attention over [compressed pages + uncompressed tail].

    q f32 [B, KVH, G, D]; page_table i32 [B, PMAX]; lengths i32 [B] counts
    tokens resident in compressed pages; tail_k/tail_v f32 [B, KVH, page, D]
    is the per-sequence write buffer with tail_len i32 [B] valid slots.
    """
    return _paged_attention_tail(q, pages, page_table, lengths,
                                 tail_k, tail_v, tail_len,
                                 interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_attention_tail(q, pages, page_table, lengths,
                          tail_k, tail_v, tail_len, *, interpret):
    bsz, kvh, g, d = q.shape
    pmax = page_table.shape[1]
    page = pages.kd.shape[2]
    kb, ks, vb, vs = _table_scales(pages, page_table)
    sc_map = _scale_map(pmax)

    def kv_map(b_i, h_i, p_i, pt, ln, tl):
        del ln, tl
        # Grid step pmax is the tail step; clamp so its (unused) page DMA
        # stays in bounds.
        return (pt[b_i, jnp.minimum(p_i, pmax - 1)], h_i, 0, 0)

    def bh_map(b_i, h_i, p_i, pt, ln, tl):
        del p_i, pt, ln, tl
        return (b_i, h_i, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bsz, kvh, pmax + 1),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), bh_map),
            pl.BlockSpec((1, 1, page, d), kv_map),
            pl.BlockSpec((1, 1, page, 1), sc_map),
            pl.BlockSpec((1, 1, page, 1), sc_map),
            pl.BlockSpec((1, 1, page, d), kv_map),
            pl.BlockSpec((1, 1, page, 1), sc_map),
            pl.BlockSpec((1, 1, page, 1), sc_map),
            pl.BlockSpec((1, 1, page, d), bh_map),
            pl.BlockSpec((1, 1, page, d), bh_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), bh_map),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
            pltpu.VMEM((g, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        _paged_attn_tail_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, kvh, g, d), jnp.float32),
        interpret=interpret,
    )(page_table, lengths, tail_len,
      q, pages.kd, kb, ks, pages.vd, vb, vs, tail_k, tail_v)
