"""Pallas TPU kernel: paged decode attention fused with BDI-KV dequant.

This is the flagship kernel: the LCP-style compressed KV page pool
(int8 deltas + per-(token, head) base/scale — see DESIGN.md §2.2) is read
*directly* in its compressed form; dequantization fuses into the
flash-decoding inner loop, so HBM traffic for K/V is ~the int8 bytes.
This realizes the thesis' §5.5.1 "bandwidth reduction" optimization where it
matters on TPU: decode attention is HBM-bandwidth-bound.

Work follows each row's valid pages, not the padded page table: the grid
is one step per batch row, and inside it a loop walks the row's
``ceil(ceil(lengths[b] / page) / ppb)`` blocks of ``ppb = 128 // page``
pages (128 tokens, one lane tile), every KV head at once.  The pools stay
in HBM; each valid page is copied into VMEM with its own DMAs
(``pltpu.make_async_copy``: the int8 K and V pages of all heads, and the
page's base/scale row), double-buffered so block ``i + 1`` streams in
while block ``i`` computes.  A page past the row's length is never
copied, so a row with no pages (an inactive slot, a tail-only row) only
attends its tail.

Base and scale are applied along the score's lane axis —
``s = ks * (q . kd) + kb * sum(q)`` and ``o = (p * vs) . vd + p . vb`` —
which is the same dequantisation as ``kd * ks + kb`` under the same f32
online softmax, without moving a per-token column across lanes.  The
pool's ``[P, KVH, page]`` scale arrays have a minor dimension too narrow
to slice a page out of on TPU, so each call packs them into one
lane-dense ``[P, 1, W]`` row per page (``_scale_rows``): loop-invariant,
so XLA does it once per decode step, outside the layer loop.

Two entry points share the body:

  * ``paged_attention``       — compressed pages only;
  * ``paged_attention_tail``  — compressed pages **plus** one uncompressed
    f32 tail block per sequence (the serving engine's write buffer), fused
    as the last step of each row so decode attention over [pages + tail]
    is a single kernel launch.  This is what ``serving/engine.py`` runs
    on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._backend import resolve_interpret
from .ref import CompressedKVPages

LANES = 128            # tokens per block: one lane tile of scores


def _pages_per_block(page: int) -> int:
    """Pages the kernel copies and attends per loop step."""
    assert LANES % page == 0, f"page {page} must divide {LANES}"
    return LANES // page


def _scale_rows(pages: CompressedKVPages) -> jax.Array:
    """Each page's K base, K scale, V base and V scale, one lane-dense row
    per page: f32 [P, 1, W], segment ``a * KVH + h`` (a in kb, ks, vb,
    vs) at lanes ``[seg * page, (seg + 1) * page)``, zero-padded to a
    multiple of 128 lanes."""
    n = pages.kb.shape[0]
    parts = [x.reshape(n, 1, -1) for x in (pages.kb, pages.ks,
                                           pages.vb, pages.vs)]
    pad = -sum(x.shape[2] for x in parts) % LANES
    if pad:
        parts.append(jnp.zeros((n, 1, pad), jnp.float32))
    return jnp.concatenate(parts, axis=2)


def _stagger(rows, page: int):
    """rows f32 [ppb, W] (row j = page j's scale row) -> the same rows
    with row j rotated right by ``j * page`` lanes."""
    ppb = rows.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
    out = rows
    for j in range(1, ppb):
        out = jnp.where(row == j, pltpu.roll(rows, j * page, 1), out)
    return out


def _lane_row(staggered, seg: int, page: int):
    """[1, ppb * page] whose lane ``j * page + t`` is segment ``seg``'s
    token t of page j: a lane rotation of the staggered rows
    (:func:`_stagger`) brings each row's copy of the segment to lane
    ``j * page``, and a masked sum over the rows keeps each row's own
    piece."""
    ppb, w = staggered.shape
    span = ppb * page
    r = pltpu.roll(staggered, (w - seg * page) % w, 1)[:, :span]
    row = jax.lax.broadcasted_iota(jnp.int32, (ppb, span), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (ppb, span), 1)
    return jnp.sum(jnp.where(lane // page == row, r, 0.0), axis=0,
                   keepdims=True)


def _tail_update(q, k, v, valid, m, l, acc):
    """Online-softmax update with an f32 tail block; robust to a block
    (and a history) with no valid token."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.where(valid, s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
    alpha = jnp.where(m == -jnp.inf, 0.0, jnp.exp(m - m_safe))
    p = jnp.where(s == -jnp.inf, 0.0, jnp.exp(s - m_safe))
    acc = acc * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return m_new, l * alpha + jnp.sum(p, axis=1, keepdims=True), acc


def _walk_kernel(pt_ref, len_ref, *refs, page: int, has_tail: bool):
    """One batch row: its valid page blocks, then (optionally) its tail."""
    if has_tail:
        (tlen_ref, q_ref, kd_hbm, vd_hbm, sc_hbm, tk_ref, tv_ref,
         out_ref, kbuf, vbuf, sbuf, sem) = refs
    else:
        (q_ref, kd_hbm, vd_hbm, sc_hbm, out_ref,
         kbuf, vbuf, sbuf, sem) = refs
    b = pl.program_id(0)
    _, ppb, kvh, _, d = kbuf.shape
    w = sbuf.shape[-1]
    g = q_ref.shape[2]
    pmax = pt_ref.shape[1]
    span = ppb * page
    length = len_ref[b]
    n_pages = (length + page - 1) // page
    n_blocks = (n_pages + ppb - 1) // ppb

    def block_copies(blk, slot):
        """(condition, DMAs) of each page of block ``blk``."""
        out = []
        for j in range(ppb):
            p = blk * ppb + j
            pid = pt_ref[b, jnp.minimum(p, pmax - 1)]
            out.append((p < n_pages, [
                pltpu.make_async_copy(src.at[pid], dst.at[slot, j],
                                      sem.at[slot])
                for src, dst in ((kd_hbm, kbuf), (vd_hbm, vbuf),
                                 (sc_hbm, sbuf))]))
        return out

    def start(blk, slot):
        for cond, dmas in block_copies(blk, slot):
            @pl.when(cond)
            def _():
                for dma in dmas:
                    dma.start()

    def wait(blk, slot):
        for cond, dmas in block_copies(blk, slot):
            @pl.when(cond)
            def _():
                for dma in dmas:
                    dma.wait()

    q = q_ref[0] * jax.lax.rsqrt(jnp.float32(d))             # [KVH, g, d]
    qsum = jnp.sum(q, axis=2, keepdims=True)                 # [KVH, g, 1]

    @pl.when(n_blocks > 0)
    def _():
        start(0, 0)

    def step(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _():
            start(i + 1, 1 - slot)

        wait(i, slot)
        pos = i * span + jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
        valid = pos < length                                 # [1, span]
        rows = _stagger(sbuf[slot].reshape(ppb, w), page)
        out = []
        for h in range(kvh):
            m, l, acc = carry[h]
            kd = kbuf[slot, :, h].astype(jnp.float32).reshape(span, d)
            vd = vbuf[slot, :, h].astype(jnp.float32).reshape(span, d)
            kb, ks, vb, vs = (_lane_row(rows, a * kvh + h, page)
                              for a in range(4))
            s = ks * jax.lax.dot_general(
                q[h], kd, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) + kb * qsum[h]
            s = jnp.where(valid, s, -jnp.inf)                # [g, span]
            # the block holds a valid token, so m_new is finite
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            # lanes past the row's pages hold whatever the buffer last
            # held (NaN before its first use): select, don't multiply
            pv = jnp.where(valid, p * vs, 0.0)
            pb = jnp.sum(jnp.where(valid, p * vb, 0.0), axis=1,
                         keepdims=True)
            acc = acc * alpha + pb + jax.lax.dot_general(
                pv, vd, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            out.append((m_new, l * alpha + jnp.sum(p, axis=1, keepdims=True),
                        acc))
        return tuple(out)

    init = tuple((jnp.full((g, 1), -jnp.inf, jnp.float32),
                  jnp.zeros((g, 1), jnp.float32),
                  jnp.zeros((g, d), jnp.float32)) for _ in range(kvh))
    state = jax.lax.fori_loop(0, n_blocks, step, init)

    for h in range(kvh):
        m, l, acc = state[h]
        if has_tail:
            slot_valid = (jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
                          < tlen_ref[b])
            m, l, acc = _tail_update(q[h], tk_ref[0, h], tv_ref[0, h],
                                     slot_valid, m, l, acc)
        out_ref[0, h] = acc / l


def _walk(q, pages, page_table, lengths, tail, interpret):
    """The kernel's pallas_call; ``tail`` is (tail_k, tail_v, tail_len)
    or None."""
    bsz, kvh, g, d = q.shape
    page = pages.kd.shape[2]
    ppb = _pages_per_block(page)
    rows = _scale_rows(pages)

    def row_map(b_i, *_):
        return (b_i, 0, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, kvh, g, d), row_map), hbm, hbm, hbm]
    args = [q, pages.kd, pages.vd, rows]
    prefetch = [page_table, lengths]
    if tail is not None:
        tail_k, tail_v, tail_len = tail
        in_specs += [pl.BlockSpec((1, kvh, page, d), row_map)] * 2
        args += [tail_k, tail_v]
        prefetch.append(tail_len)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(bsz,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kvh, g, d), row_map),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, kvh, page, d), pages.kd.dtype),
            pltpu.VMEM((2, ppb, kvh, page, d), pages.vd.dtype),
            pltpu.VMEM((2, ppb) + rows.shape[1:], jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_walk_kernel, page=page,
                          has_tail=tail is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, kvh, g, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*prefetch, *args)


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
    return _walk(q, pages, page_table, lengths, None, interpret)


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
    return _walk(q, pages, page_table, lengths,
                 (tail_k, tail_v, tail_len), interpret)
