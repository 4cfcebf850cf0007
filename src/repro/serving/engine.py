"""Serving runtime: LCP-paged compressed KV cache + CAMP pool management.

The inference-side integration of all three thesis pillars:

  * KV pages are stored **compressed** through a pluggable
    :class:`~repro.codecs.PageCodec` (default: the single-base BDI int8
    row form, whose layout the fused Pallas decode kernel reads —
    kernels/paged_attention.py; ``codec="zero"``/``"raw"`` swap in the
    zero-page fast path / uncompressed fallback without touching the
    engine);
  * page addressing is **LCP**: fixed target size per page, page table ->
    pool index, one shift to locate a token (no prefix sums);
  * the finite HBM page pool is managed by **CAMP**-style value scoring:
    when the pool is full, the least-valuable sequence (value =
    reuse-proxy / compressed size, the MVE function) is preempted.

Serving hot path
----------------
Both halves of the lifecycle are batched, jit-compiled and
device-resident: prompts run through a **chunked-batch prefill**
(:func:`_prefill_chunk` — every admitted prompt advances ``prefill_chunk``
tokens per dispatch, one ``lax.scan`` over the stacked layer params, each
layer's K/V projection computed exactly once and shared between attention
and the page-fill path via ``gqa_forward(kv=...)``), and decode is a
single batched step (:func:`_decode_step`): all active sequences and all
layers advance one token per dispatch.

Prefill keeps an exact f32 K/V scratch for the duration of the prompt
and attends under the **canonical-prefix contract** (shared with decode
and the reference oracle; see serving/prefix_cache.py): each query reads
the compress-then-dequantize round trip of every completed earlier page
and exact values inside its own partial page.  That makes every
published page a pure function of the token prefix it covers —
independent of chunking, batching, or scheduling — which is what lets
the **prefix cache** share pages across requests with bit-identical
output.  Every page a chunk completes is compressed and scattered into
the device pools by the same batched page-fill dispatch decode uses
(and, when a :class:`~repro.serving.prefix_cache.PrefixCache` is
attached, registered there for cross-request reuse); the final partial
page lands in the decode tail buffers.  No per-sequence host round-trips
of KV data on either path.

With a prefix cache attached, admission looks up each prompt's longest
cached page-boundary prefix, pins the entry chain, maps the shared pool
pages straight into the new sequence's page table, and starts chunked
prefill at the first uncached boundary — cohort members carry **per-row
start offsets** through one shared relative chunk grid, so warm and cold
prompts mix in the same static-shape dispatch.  Prefill stores KV for
every prompt token but the last: the first decode step computes the last
prompt token's K/V exactly once into the tail (this fixed the historical
"duplicated last prompt key" oracle quirk — see serving/README.md).

  * The compressed page pools (``kd/kb/ks/vd/vb/vs``) live as device
    ``jnp`` arrays for the whole engine lifetime; page publishes scatter
    into them with donated ``.at[]`` writes — no host round-trips of KV
    data on the token path.  All layers share one pool: a page id names
    one layer's page, and every layer has its own page table.
  * The step embeds the last token of every sequence, runs a
    ``lax.scan`` over the stacked per-layer block params, and finishes
    with the LM head + greedy argmax — one XLA computation per token
    across the whole batch.
  * Page tables are padded to a static ``PMAX`` (doubled on demand, which
    retraces at most a handful of times) so shapes stay static across
    steps; inactive batch slots ride along masked (the fused kernel
    walks only each row's valid pages, so they cost it only a tail).
  * Attention over [compressed pages + uncompressed tail] selects its
    implementation by backend and codec: on TPU a codec that ships a
    fused kernel (BDI: the fused-dequant Pallas kernel,
    ``kernels.paged_attention_tail``) reads the pool in compressed form;
    elsewhere a generic gather-decompress-dense jnp fallback runs inside
    the same jit (the ``use_fused`` ctor arg overrides the detection).
  * Page-fill compression is batched: every freshly filled tail of every
    layer is compressed in one jitted dispatch
    (:func:`_compress_blocks`), which also computes per-page compressed
    byte counts **on device**; the counts sync to the host once per
    publish and drive the host-side CAMP preemption policy.

Tokens accumulate in an *uncompressed tail* page per (layer, sequence)
— the write buffer, also device-resident; when the tail fills, it is
compressed and published to the pool, off the critical path, exactly
like the thesis' cache-fill-side compression.

The host keeps only control state: token ids, page-table lists, the
free-page list, and CAMP accounting.  ``serving/reference.py`` holds the
original single-sequence host-looped engine as the behavioral oracle.

Equivalence contract vs the reference: greedy output is token-for-token
identical while no preemption fires, and through preemptions whose
victim choice is order-independent (e.g. a ``done`` sequence, CAMP value
-1).  Caveat: when two logits land within one bf16 ULP of each other (a
true tie at model precision), the padded-softmax summation order can
pick the other token — observed roughly once per ~20 tokens on random
tiny-model prompts, never with a materially-separated argmax.  When live sequences with near-equal CAMP values compete for
eviction, victim choice can differ: the reference interleaves publishes
between sequences inside a round while the batched step publishes once
after all sequences advanced, so the two engines observe value sets at
slightly different times.  That is inherent to batching, not a bug.
"""

from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import codecs
from repro.configs.base import ArchConfig
from repro.core.camp import _pow2_bucket
from repro.kernels._backend import default_interpret
from repro.models import attention as A
from repro.models import layers as L
from repro.serving import faults as F
from repro.serving import tier as T
from repro.serving.telemetry import Telemetry
from repro.serving.prefix_cache import (PrefixCache, canonical_update,
                                        prefix_chunk_attention)


@dataclass
class Sequence:
    sid: int
    slot: int                            # batch slot in the device state
    tokens: list[int]
    pages: list[list[int]]               # [L][n_pages] pool ids
    tail_len: int = 0
    done: bool = False
    preempted: bool = False
    corrupted: bool = False              # failed a page-integrity check
    prefilling: bool = False             # in-flight admission cohort member
    # prefix-cache chain: entry ids whose pages this sequence maps, in
    # block order.  pages[li][:len(chain)] are shared (cache-owned);
    # the rest are private and freed on release/preemption.
    chain: list[int] = field(default_factory=list)


@dataclass
class _Cohort:
    """In-flight chunked-prefill admission cohort.

    All members share one *relative* chunk grid: every dispatch advances
    the grid offset ``roff`` by up to ``prefill_chunk`` tokens (less when
    the scheduler's token budget splits a chunk).  Member ``i`` starts at
    its own absolute offset ``starts[i]`` (its prefix-cache hit boundary,
    0 when cold), so its chunk this dispatch covers absolute positions
    ``starts[i] + roff ..`` — per-row offsets through one static-shape
    dispatch.  ``toks`` is the host-side zero-padded prompt buffer
    (absolute positions); ``kscr/vscr`` the device-resident exact f32 K/V
    scratch (absolute positions; warm rows carry the dequantized cached
    prefix below ``starts[i]``); ``pub[i]`` counts pages already
    published or mapped for ``seqs[i]``; ``done_sids`` tracks members
    whose prefill completed (tail written).
    """
    seqs: list[Sequence]
    row: dict[int, int]                  # sid -> scratch row
    toks: np.ndarray                     # [nrows, tmax] i32, host
    kscr: jax.Array                      # [L, nrows, tmax, K, D] f32 exact
    vscr: jax.Array
    kcan: jax.Array                      # canonical (codec round-trip) view
    vcan: jax.Array                      # of completed pages; zero-length
                                         # T axis for lossless codecs
    starts: list[int]                    # absolute start offset per member
    maxrel: int                          # grid length: max stored-start
    roff: int = 0                        # relative grid offset
    pub: list[int] | None = None
    done_sids: set[int] | None = None


# ---------------------------------------------------------------------------
# jitted device steps
# ---------------------------------------------------------------------------

def _attend_ref(codec, q, pools, pt, page_len, tk, tv, tail_len):
    """jnp fallback: gather-then-decompress pages + tail, dense softmax.

    q f32 [S, K, G, D]; pools the codec's page pool pytree
    (leaves leading [P]); pt i32 [S, PMAX]; tk/tv f32 [S, K, page, D].
    Gathers compressed bytes first so only [S, PMAX] pages decompress,
    not the whole pool.
    """
    s, kvh, g, d = q.shape
    pmax = pt.shape[1]
    page = tk.shape[2]

    kg, vg = codec.decompress_pages(
        jax.tree.map(lambda a: a[pt], pools))        # [S,PMAX,K,page,D] f32
    kg = jnp.moveaxis(kg, 2, 1).reshape(s, kvh, pmax * page, d)
    vg = jnp.moveaxis(vg, 2, 1).reshape(s, kvh, pmax * page, d)
    kg = jnp.concatenate([kg, tk], axis=2)           # [S, K, T, D]
    vg = jnp.concatenate([vg, tv], axis=2)

    pos = jnp.arange(pmax * page)[None, :]
    valid = jnp.concatenate(
        [pos < page_len[:, None],
         jnp.arange(page)[None, :] < tail_len[:, None]], axis=1)

    sc = jnp.einsum("skgd,sktd->skgt", q, kg) / jnp.sqrt(jnp.float32(d))
    sc = jnp.where(valid[:, None, None, :], sc, -jnp.inf)
    w = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("skgt,sktd->skgd", w, vg)


def _decode_core(params, pools, tk, tv, page_table, page_cnt,
                 last_tok, pos, tail_len, active, *, cfg: ArchConfig,
                 codec: codecs.PageCodec, use_fused: bool):
    """One greedy decode step for every active sequence, all layers.

    pools: the codec's page-pool pytree (leaves [P, ...]), read by
    every layer through its own page table.  tk/tv f32
    [L, S, K, page, D] (donated by the jit wrappers; returned
    updated).  page_table i32 [L, S, PMAX];
    page_cnt/last_tok/pos/tail_len i32 [S]; active bool [S].  Returns
    (next_tok [S], tk', tv').

    Shared trace body: dispatched standalone via :func:`_decode_step` or
    fused with a prefill chunk via :func:`_mixed_step`.
    """
    s = last_tok.shape[0]
    kvh, dh = cfg.n_kv_heads, cfg.head_dim
    x = L.embed(params["embed"], last_tok[:, None])          # [S, 1, D]
    cos, sin = L.rope_angles(pos, dh, cfg.rope_theta)        # [S, dh/2]
    cos_b = cos[:, None, None, :]
    sin_b = sin[:, None, None, :]
    page_len = page_cnt * tk.shape[3]                        # tokens in pages
    # tail write slot, masked so inactive sequences' buffers stay untouched
    slot_hot = ((jnp.arange(tk.shape[3])[None, :] == tail_len[:, None])
                & active[:, None])                           # [S, page]

    def body(x, xs):
        bp, tk_l, tv_l, pt_l = xs
        h = L.rmsnorm(bp["ln1"], x, cfg.norm_eps)
        q = L.linear(bp["attn"]["wq"], h)                    # [S, 1, H, Dh]
        k_new = L.linear(bp["attn"]["wk"], h)                # [S, 1, K, Dh]
        v_new = L.linear(bp["attn"]["wv"], h)
        q = L.apply_rope(q, cos_b, sin_b)
        k_new = L.apply_rope(k_new, cos_b, sin_b)

        # append the new token into the tail write buffer [S, K, page, D]
        kw = k_new[:, 0].astype(jnp.float32)                 # [S, K, Dh]
        vw = v_new[:, 0].astype(jnp.float32)
        sel = slot_hot[:, None, :, None]
        tk_l = jnp.where(sel, kw[:, :, None, :], tk_l)
        tv_l = jnp.where(sel, vw[:, :, None, :], tv_l)

        hq = q.shape[2]
        qg = q[:, 0].reshape(s, kvh, hq // kvh, dh).astype(jnp.float32)
        if use_fused:
            ctx = codec.paged_attention_tail(qg, pools, pt_l, page_len,
                                             tk_l, tv_l, tail_len + 1)
        else:
            ctx = _attend_ref(codec, qg, pools, pt_l, page_len,
                              tk_l, tv_l, tail_len + 1)
        ctx = ctx.reshape(s, 1, hq, dh).astype(x.dtype)
        x = x + A._proj_out(bp["attn"], ctx)
        h2 = L.rmsnorm(bp["ln2"], x, cfg.norm_eps)
        x = x + L.mlp(bp["ffn"], h2)
        return x, (tk_l, tv_l)

    xs = (params["blocks"], tk, tv, page_table)
    x, (tk, tv) = jax.lax.scan(body, x, xs)
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = L.lm_logits(params["lm_head"], x)[:, 0]         # [S, V]
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.where(active, nxt, last_tok), tk, tv


@functools.partial(jax.jit,
                   static_argnames=("cfg", "codec", "use_fused"),
                   donate_argnums=(2, 3))
def _decode_step(params, pools, tk, tv, page_table, page_cnt,
                 last_tok, pos, tail_len, active, *, cfg: ArchConfig,
                 codec: codecs.PageCodec, use_fused: bool):
    """Decode-only dispatch (no prefill chunk riding along)."""
    return _decode_core(params, pools, tk, tv, page_table, page_cnt,
                        last_tok, pos, tail_len, active, cfg=cfg,
                        codec=codec, use_fused=use_fused)


def _row_update(scr, val, offs):
    """Per-row dynamic_update_slice: scr [R, T, K, D] <- val [R, C, K, D]
    at row-specific offsets offs [R] (pre-clamped to T - C by the host)."""
    return jax.vmap(
        lambda s, v, o: jax.lax.dynamic_update_slice(s, v, (o, 0, 0))
    )(scr, val, offs)


def _prefill_core(params, tokens, kscr, vscr, kcan, vcan, offs, *,
                  cfg: ArchConfig, page: int, codec: codecs.PageCodec):
    """One chunked-batch prefill step: C prompt tokens per row, all layers.

    tokens i32 [R, C] (one scratch row per admitted prompt, zero-padded);
    offs i32 [R] — each row's absolute chunk start (``starts[i] + roff``:
    rows advance one shared relative grid from per-row start offsets, so
    warm prefix-cache hits and cold prompts mix in one static dispatch;
    padded rows compute masked garbage that is never published).
    kscr/vscr f32 [L, R, Tmax, K, D] are the donated *exact* f32 K/V
    scratch, absolute-indexed; kcan/vcan its carried canonical view
    (codec round trip of completed pages; warm rows carry the
    dequantized cached prefix, filled at admission and never
    re-compressed).

    Attention follows the canonical-prefix contract (see
    serving/prefix_cache.py): each query reads the canonical values of
    every completed earlier page and exact values inside its own page —
    chunk-layout-independent, which keeps warm/cold and chunked/blocking
    paths token-for-token identical.  Only the window of pages the chunk
    touches is re-round-tripped (``canonical_update``), so per-prompt
    canonicalization work is O(T), not O(T^2 / chunk).  Returns the
    updated scratch + canonical view; page extraction/compression
    happens in follow-up dispatches (:func:`_gather_prefill_blocks` +
    :func:`_publish_blocks`).

    Lossless codecs (``codec.lossless``: roundtrip == identity) skip the
    roundtrip entirely — canonical values equal exact values, so the
    chunk attends its own scratch through the single-einsum ``identity``
    attention and kcan/vcan ride through untouched (the engines allocate
    them zero-length).  This claws back the canonical contract's
    roundtrip + second-einsum cost wherever the codec makes it free.
    """
    r, c = tokens.shape
    kvh, dh = cfg.n_kv_heads, cfg.head_dim
    x = L.embed(params["embed"], tokens)                     # [R, C, D]
    qpos = offs[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    cos, sin = L.rope_angles(qpos, dh, cfg.rope_theta)       # [R, C, dh/2]
    cos_b, sin_b = cos[:, :, None, :], sin[:, :, None, :]

    def body(carry, bp):
        x, li, bufs = carry
        kscr_l, vscr_l, kcan_l, vcan_l = (buf[li] for buf in bufs)
        h = L.rmsnorm(bp["ln1"], x, cfg.norm_eps)
        # one K/V projection per layer through the shared path (per-row
        # positions), feeding both the scratch write and attention
        k, v = A.gqa_kv(bp["attn"], h, qpos, theta=cfg.rope_theta)
        q = L.apply_rope(L.linear(bp["attn"]["wq"], h), cos_b, sin_b)
        kscr_l = _row_update(kscr_l, k.astype(jnp.float32), offs)
        vscr_l = _row_update(vscr_l, v.astype(jnp.float32), offs)
        hq = q.shape[2]
        qg = q.reshape(r, c, kvh, hq // kvh, dh).astype(jnp.float32)
        if codec.lossless:
            ctx = prefix_chunk_attention(qg, qpos, kscr_l, vscr_l,
                                         kscr_l, vscr_l, page,
                                         identity=True)
        else:
            kcan_l, vcan_l = canonical_update(kscr_l, vscr_l, kcan_l,
                                              vcan_l, offs, page,
                                              c + page, codec)
            ctx = prefix_chunk_attention(qg, qpos, kscr_l, vscr_l,
                                         kcan_l, vcan_l, page)
        x = x + A._proj_out(bp["attn"], ctx.reshape(r, c, hq, dh)
                            .astype(x.dtype))
        h2 = L.rmsnorm(bp["ln2"], x, cfg.norm_eps)
        x = x + L.mlp(bp["ffn"], h2)
        bufs = tuple(jax.lax.dynamic_update_index_in_dim(buf, new, li, 0)
                     for buf, new in zip(bufs, (kscr_l, vscr_l, kcan_l,
                                                vcan_l)))
        return (x, li + 1, bufs), None

    # the scratch rides the scan carry and is updated in place; as scan
    # xs/ys it would get fresh stacked output buffers beside the donated
    # inputs (2 GiB at yi-6b's widths for an 8-row, 1024-token cohort)
    (_, _, bufs), _ = jax.lax.scan(
        body, (x, jnp.int32(0), (kscr, vscr, kcan, vcan)), params["blocks"])
    return bufs


@functools.partial(jax.jit, static_argnames=("cfg", "page", "codec"),
                   donate_argnums=(2, 3, 4, 5))
def _prefill_chunk(params, tokens, kscr, vscr, kcan, vcan, offs, *,
                   cfg: ArchConfig, page: int, codec: codecs.PageCodec):
    """Prefill-only dispatch (no decode step riding along)."""
    return _prefill_core(params, tokens, kscr, vscr, kcan, vcan, offs,
                         cfg=cfg, page=page, codec=codec)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "page", "codec", "use_fused"),
                   donate_argnums=(2, 3, 4, 5, 6, 7))
def _mixed_step(params, pools, tk, tv, kscr, vscr, kcan, vcan, page_table,
                page_cnt, last_tok, pos, tail_len, active, ptoks, offs, *,
                cfg: ArchConfig, page: int, codec: codecs.PageCodec,
                use_fused: bool):
    """Sarathi-style mixed iteration: one decode step for every active
    batch slot **plus** one prefill chunk for the in-flight admission
    cohort, in a single jitted dispatch.

    The two halves are data-independent (decode reads the pools/tails,
    prefill writes only its own scratch), so XLA schedules them as one
    fused computation — the prefill chunk piggybacks on the decode
    iteration instead of stalling it.  All shapes are static given
    (max_batch, PMAX, cohort scratch size, prefill_chunk), so admitting
    and retiring requests between steps never retraces; per-row prefill
    offsets arrive as traced data, so prefix-cache hit boundaries don't
    retrace either.
    """
    nxt, tk, tv = _decode_core(params, pools, tk, tv, page_table, page_cnt,
                               last_tok, pos, tail_len, active, cfg=cfg,
                               codec=codec, use_fused=use_fused)
    kscr, vscr, kcan, vcan = _prefill_core(
        params, ptoks, kscr, vscr, kcan, vcan, offs, cfg=cfg, page=page,
        codec=codec)
    return nxt, tk, tv, kscr, vscr, kcan, vcan


@functools.partial(jax.jit, static_argnames=("codec",),
                   donate_argnums=(0, 1, 2, 3))
def _fill_warm_scratch(kscr, vscr, kcan, vcan, pools, wpt, wlen, *,
                       codec: codecs.PageCodec):
    """Decompress cached prefix pages into the scratch warm regions.

    kscr/vscr/kcan/vcan [L, R, T, K, D] (donated); wpt i32 [L, R, WP]
    per-layer pool ids of each row's cached prefix chain (0-padded);
    wlen i32 [R] cached token count (page-aligned).  The written values
    are exactly what decode-side paged attention reads for those pages —
    canonical by construction — so both the exact scratch and the
    canonical view receive them verbatim, and ``canonical_update`` never
    re-compresses the warm region (its windows start at or after the hit
    boundary).  For a lossless codec the canonical view is unused (and
    zero-length); only the exact scratch is filled.
    """
    lyr, r, t, kvh, dh = kscr.shape
    wp = wpt.shape[2]

    def deq_layer(pt_l):
        return codec.decompress_pages(jax.tree.map(lambda a: a[pt_l], pools))

    kw, vw = jax.vmap(deq_layer)(wpt)                 # [L, R, WP, K, pg, D]
    page = kw.shape[4]

    def flat(x):
        return jnp.moveaxis(x, 3, 4).reshape(lyr, r, wp * page, kvh, dh)

    kw, vw = flat(kw), flat(vw)
    m = (jnp.arange(wp * page) < wlen[:, None])[None, :, :, None, None]

    def fill(buf, warm):
        return buf.at[:, :, :wp * page].set(
            jnp.where(m, warm, buf[:, :, :wp * page]))

    kscr, vscr = fill(kscr, kw), fill(vscr, vw)
    if not codec.lossless:
        kcan, vcan = fill(kcan, kw), fill(vcan, vw)
    return kscr, vscr, kcan, vcan


def _scratch_blocks(kscr, vscr, rows, blks, page: int):
    """Gather page blocks [L, m, K, page, D] from the prefill scratch.

    (rows[j], blks[j]) selects scratch row j's page ``blks[j]`` (token
    positions blk*page..(blk+1)*page) from the [L, R, Tmax, K, D] scratch.
    """
    lyr, r, tmax, kvh, dh = kscr.shape
    kp = kscr.reshape(lyr, r, tmax // page, page, kvh, dh)
    vp = vscr.reshape(lyr, r, tmax // page, page, kvh, dh)
    return (jnp.moveaxis(kp[:, rows, blks], 2, 3),
            jnp.moveaxis(vp[:, rows, blks], 2, 3))


@functools.partial(jax.jit, static_argnames=("page",))
def _gather_prefill_blocks(kscr, vscr, rows, blks, *, page: int):
    """Scratch -> freshly completed publish blocks [L*m, K, page, D],
    layer-major, as :func:`_publish_blocks` expects."""
    kb, vb = _scratch_blocks(kscr, vscr, rows, blks, page)
    return (kb.reshape((-1,) + kb.shape[2:]),
            vb.reshape((-1,) + vb.shape[2:]))


@functools.partial(jax.jit, static_argnames=("page",), donate_argnums=(0, 1))
def _write_tails(tail_k, tail_v, kscr, vscr, rows, slots, blks, *,
                 page: int):
    """Scatter each sequence's final partial page from the prefill scratch
    (row ``rows[j]``) into its decode tail slot ``slots[j]`` in the
    [L, S, K, page, D] tail buffers (donated)."""
    kb, vb = _scratch_blocks(kscr, vscr, rows, blks, page)
    return tail_k.at[:, slots].set(kb), tail_v.at[:, slots].set(vb)


@jax.jit
def _gather_tail_blocks(tk, tv, slots):
    """[L, S, K, page, D] tails -> [L*m, K, page, D] publish blocks."""
    kb = tk[:, slots]                                        # [L, m, K, pg, D]
    vb = tv[:, slots]
    return (kb.reshape((-1,) + kb.shape[2:]),
            vb.reshape((-1,) + vb.shape[2:]))


@functools.partial(jax.jit,
                   static_argnames=("codec", "use_fused", "member_sizes"),
                   donate_argnums=(0,))
def _publish_blocks(pools, k_blocks, v_blocks, pids, *,
                    codec: codecs.PageCodec, use_fused: bool = False,
                    member_sizes: bool = False):
    """Compress [n, K, page, D] KV blocks and scatter them into the pools.

    One dispatch publishes every filled page of every layer: the batched
    page-fill compression + donated in-place pool update.  Returns the
    updated pools, the codec's device-computed per-page byte counts [n]
    (the numbers CAMP values and SIP retention consume), and the
    per-page integrity checksums [n] (``faults.page_checksums`` over the
    freshly compressed bytes — computed here so integrity costs zero
    extra dispatches or host syncs; verification recomputes the same
    function over the pool bytes at the trust boundaries).
    ``use_fused`` routes compression through the codec's fused kernel
    path (BDI: the Pallas row codec, bit-exact with the jnp oracle)
    where it compiles natively.

    Also returns the per-page codec-id tags [n] (``codec.page_tags``):
    zeros for single-algorithm codecs, the winning member id for the
    adaptive composite.  Computed inside this dispatch so the tag rides
    the same host sync as bytes and checksums.

    ``member_sizes`` (static; observatory-only, requires a composite
    codec with ``members``) additionally returns every member codec's
    *would-be* per-page byte counts [n_members, n] — the adaptive
    compress already produced each member's encoding, so this is a
    per-member ``page_nbytes`` reduction riding the same dispatch and
    host sync, feeding the what-if codec sampling
    (``serving/shadow.CodecShadow``).  ``None`` when off, so default
    traces are unchanged.
    """
    compress = (codec.compress_kv_pages_fused if use_fused
                else codec.compress_kv_pages)
    pg = compress(k_blocks, v_blocks)
    nbytes = codec.page_nbytes(pg)
    csums = F.page_checksums(pg)
    tags = codec.page_tags(pg)
    msizes = None
    if member_sizes:
        msizes = jnp.stack(
            [m.page_nbytes(c) for m, c in
             zip(codec.members, codec._member_pages(pg))])
    pools = jax.tree.map(lambda pool, new: pool.at[pids].set(new),
                         pools, pg)
    return pools, nbytes, csums, tags, msizes


@jax.jit
def _gather_entry_pages(pools, pids):
    """Gather one entry's per-layer compressed pages (``pids`` i32 [L])
    out of the pools: leaves [L, ...], the tier's demotion payload."""
    return jax.tree.map(lambda a: a[pids], pools)


@functools.partial(jax.jit, donate_argnums=(0,))
def _promote_scatter(pools, vals, pids):
    """Scatter a batch of tier records' leaves ([N, L, ...]) back into
    the pools at ``pids`` i32 [N, L] — the promotion twin of the publish
    scatter.  One dispatch covers a whole promoted chain; per-block
    dispatch would make warm promotion scale like cold prefill at small
    model sizes.  Callers pad N to a power of two (rows aimed at pool
    page 0, the padding target) so retrace count stays logarithmic in
    chain length."""
    return jax.tree.map(lambda pool, v: pool.at[pids].set(v), pools, vals)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

class PagedKVEngine:
    """Greedy-decoding engine over a dense-GQA transformer.

    Batched device-resident hot path; see the module docstring.  The
    public surface matches the seed engine (``add_request`` /
    ``decode_one`` / stats) plus :meth:`add_requests` and
    :meth:`decode_batch`, the intended entry points under load.
    """

    def __init__(self, cfg: ArchConfig, params, *, page_size: int = 16,
                 n_pool_pages: int = 256, max_batch: int = 32,
                 use_fused: bool | None = None,
                 prefill_chunk: int | None = None,
                 prefix_cache: PrefixCache | None = None,
                 codec: str | codecs.PageCodec | None = None,
                 faults: "F.FaultInjector | None" = None,
                 integrity: bool = True,
                 telemetry: Telemetry | None = None,
                 observatory=None,
                 tier: "T.TieredPageStore | None" = None,
                 cache_decode_pages: bool = False):
        assert cfg.attn_kind == "gqa" and not cfg.is_encdec
        if prefix_cache is not None:
            assert prefix_cache.page == page_size \
                and prefix_cache.n_layers == cfg.n_layers, \
                "prefix cache shape disagrees with the engine"
        self.cfg = cfg
        self.params = params
        self.page = page_size
        self.max_batch = max_batch
        self.n_pool_pages = n_pool_pages
        self.prefix_cache = prefix_cache
        # page codec: name / instance / None (the REPRO_CODEC-or-bdi
        # default).  Registry singletons keep jit traces shared across
        # engines using the same codec.
        self.codec = codecs.resolve(codec)
        # chunked-prefill step width (tokens per slot per dispatch); must
        # stay page-aligned so every chunk completes whole pages
        self.prefill_chunk = (2 * page_size if prefill_chunk is None
                              else prefill_chunk)
        assert self.prefill_chunk % page_size == 0, \
            (self.prefill_chunk, page_size)
        # fused kernels where the codec brings them and Pallas compiles
        # natively; the generic jnp path elsewhere.  Attention and
        # page-fill gate separately: a codec may ship a fused fill
        # (gbdi, adaptive) without a fused attention kernel.
        want_fused = (not default_interpret()
                      if use_fused is None else use_fused)
        self.use_fused = want_fused and self.codec.has_fused_kernels
        self.use_fused_fill = want_fused and (
            self.codec.has_fused_kernels or self.codec.has_fused_fill)
        lyr, k, dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        self.pools = self.codec.init_pools(n_pool_pages, k, page_size, dh)
        self.tail_k = jnp.zeros((lyr, max_batch, k, page_size, dh),
                                jnp.float32)
        self.tail_v = jnp.zeros_like(self.tail_k)
        # pool id 0 is the padding target of padded page tables
        self.free: list[int] = list(range(n_pool_pages - 1, 0, -1))
        self.page_bytes = np.zeros(n_pool_pages, np.int64)
        # publish-time integrity checksums (serving/faults.py); consulted
        # only for currently-mapped pages, so stale slots are harmless
        self.page_checksum = np.zeros(n_pool_pages, np.uint32)
        # per-page codec-id tags (Touché-style page-table metadata):
        # always 0 for single-algorithm codecs, the winning member id
        # under the adaptive composite
        self.page_codec_id = np.zeros(n_pool_pages, np.int32)
        self.integrity = integrity
        self.faults = faults
        # degradation-ladder level 1 (scheduler-driven): drop speculative
        # prefix-cache insertions while the pool is under pressure
        self.shed_cache_inserts = False
        self.seqs: dict[int, Sequence] = {}
        # cumulative published bytes per request (survives release; the
        # serving driver reports per-request compression from this)
        self.request_bytes: dict[int, list[int]] = {}
        self._free_slots = list(range(max_batch - 1, -1, -1))
        self._pmax = 8
        self._pt_dev: jax.Array | None = None
        self._pt_dirty = True
        self._cohort: _Cohort | None = None
        # registry-backed counters behind the legacy `.stats` property
        # (serving/telemetry.py); the reference oracle mirrors the same
        # series so engine-vs-oracle stats equality keeps holding
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self._init_metrics()
        if faults is not None:
            faults.telemetry = self.telemetry
        if prefix_cache is not None:
            prefix_cache.telemetry = self.telemetry
        # opt-in hierarchy observatory (serving/observatory.py): reuse
        # analytics, shadow policy/codec simulation, decision audit.
        # None keeps every hook a single attribute check, so a default
        # engine is byte-identical in behavior and metrics.
        self.obs = observatory
        if observatory is not None:
            observatory.bind_engine(self)
        # optional host/disk demotion tier (serving/tier.py).  Decode-
        # page caching is opt-in: decode-produced pages are pure
        # functions of the token prefix in principle, but decode-vs-
        # prefill summation order can differ at ULP level, so the
        # warm==cold bit-equality suites keep it off by default.
        self.tier: T.TieredPageStore | None = None
        self.cache_decode_pages = cache_decode_pages
        self._h_promote = None
        if tier is not None:
            self.attach_tier(tier)

    _STAT_KEYS = ("pages_compressed", "pages_evicted", "bytes_raw",
                  "bytes_compressed", "preemptions",
                  "prefix_pages_evicted", "shed_inserts",
                  "integrity_failures")
    # what the batched decode-attention walk covers, per dispatch times
    # layers: the active rows' pages, and the padded [max_batch, PMAX]
    # table a dense walk would visit.  The host-looped oracle has no
    # batched walk, so it carries none of these.
    _WALK_KEYS = ("attn_pages_read", "attn_pages_padded")

    def _init_metrics(self) -> None:
        reg = self.telemetry.registry
        cn = self.codec.name
        self._m = {k: reg.counter(f"engine_{k}_total", codec=cn)
                   for k in self._STAT_KEYS + self._WALK_KEYS}
        self._g_pool_used = reg.gauge(
            "engine_pool_used_pages", "mapped pool pages (id 0 excluded)")
        self._g_free = reg.gauge(
            "engine_free_list_depth", "pages on the free list")
        self._g_pressure = reg.gauge(
            "engine_pool_pressure", "non-reclaimable pool fraction [0,1]")
        # per-codec publish telemetry: under the adaptive composite each
        # page's winning member is its tag, so ratio/byte series split by
        # member name; single-algorithm codecs have one series
        members = getattr(self.codec, "members", None)
        self._tag_names = ([m.name for m in members] if members
                           else [cn])
        self._tag_metrics: dict[int, tuple] = {}

    def _publish_metrics(self, tag: int):
        tm = self._tag_metrics.get(tag)
        if tm is None:
            reg = self.telemetry.registry
            name = (self._tag_names[tag] if tag < len(self._tag_names)
                    else str(tag))
            tm = self._tag_metrics[tag] = (
                reg.counter("engine_pages_by_codec_total",
                            "published pages by winning codec",
                            codec=name),
                reg.counter("engine_compressed_bytes_by_codec_total",
                            "compressed bytes by winning codec",
                            codec=name),
                reg.histogram("engine_page_compressed_bytes",
                              "per-page compressed size", codec=name),
                reg.histogram("engine_page_compression_ratio",
                              "per-page raw/compressed ratio",
                              codec=name))
        return tm

    @property
    def stats(self) -> dict:
        """Legacy stats mapping, rebuilt from the metrics registry."""
        return {k: m.value for k, m in self._m.items()}

    def load_stats_dict(self, s: dict) -> None:
        """Restore counters from a legacy stats dict (snapshot compat)."""
        for k, m in self._m.items():
            if k in s:
                m.value = s[k]

    def sample_gauges(self) -> None:
        """Refresh pool-occupancy gauges (called before an export)."""
        self._g_pool_used.set(self.pool_used_pages())
        self._g_free.set(len(self.free))
        self._g_pressure.set(round(self.pool_pressure(), 6))
        if self.prefix_cache is not None:
            self.prefix_cache.sample_metrics()
        if self.faults is not None:
            self.faults.sample_metrics()
        obs = getattr(self, "obs", None)   # absent on the reference oracle
        if obs is not None:
            obs.sample_gauges()
        if getattr(self, "tier", None) is not None:
            self.tier.sample_metrics()

    # -- pool bookkeeping ----------------------------------------------------

    def page_raw_bytes(self) -> int:
        c = self.cfg
        return 2 * self.page * c.n_kv_heads * c.head_dim * 2   # K+V bf16

    def pool_pressure(self) -> float:
        """Non-reclaimable pool fraction in [0, 1]: pages neither free
        nor cheaply evictable (retained refcount-0 prefix entries count
        as reclaimable — they free without preempting anyone).  The
        degradation ladder's input signal."""
        cap = self.n_pool_pages - 1
        reclaimable = len(self.free)
        if self.prefix_cache is not None:
            reclaimable += self.prefix_cache.retained_pages()
        return max(0.0, 1.0 - reclaimable / cap)

    def _reserve_pages(self, n: int) -> list[int]:
        """Reclaim order under pool pressure: free list, then retained
        prefix-cache entries (SIP victim ranking — they are speculative
        state), then CAMP preemption of the least-valuable live sequence
        (which unpins its shared chain, possibly feeding the next round
        of cache eviction)."""
        while len(self.free) < n:
            if not self._evict_prefix_pages(n - len(self.free)):
                self._preempt_one()
        return [self.free.pop() for _ in range(n)]

    def _evict_prefix_pages(self, need: int) -> bool:
        if self.prefix_cache is None:
            return False
        pids = self.prefix_cache.evict_for(need)
        if not pids:
            return False
        self.free.extend(pids)
        if self.obs is not None:
            self.obs.on_release(pids)
        self._m["prefix_pages_evicted"].inc(len(pids))
        return True

    def _seq_reclaimable_bytes(self, seq: Sequence) -> int:
        """Compressed bytes preempting this sequence would make
        evictable: its private pages, plus shared prefix entries it is
        the sole pinner of (they drop to refcount 0 and free next
        reclaim round); pages still pinned by another sharer count
        nothing."""
        ns = len(seq.chain)
        size = sum(int(self.page_bytes[p])
                   for lp in seq.pages for p in lp[ns:])
        for eid in seq.chain:
            e = self.prefix_cache.entries[eid]
            if e.refcount == 1:
                size += e.nbytes
        return size

    def _seq_value(self, seq: Sequence) -> float:
        """CAMP/MVE value: reuse proxy / *reclaimable* compressed size
        (smaller = victim).  Shared prefix pages count only when this
        sequence is their sole pinner — preempting it then drops them to
        refcount 0 (evictable next reclaim round); pages still pinned by
        another sharer free nothing, so they must not make a warm
        sequence look like a cheap victim."""
        if seq.done:
            return -1.0
        return ((len(seq.tokens) + 1)
                / max(self._seq_reclaimable_bytes(seq), 1))

    def _drop_seq_pages(self, seq: Sequence, *, count_evicted: bool) -> None:
        """Detach a sequence from its pages: free the private ones, unpin
        the shared prefix chain (cache-owned pages stay resident — other
        sequences may map them; refcount-0 entries become evictable)."""
        ns = len(seq.chain)
        for lp in seq.pages:
            self.free.extend(lp[ns:])
            if self.obs is not None:
                self.obs.on_release(lp[ns:])
            if count_evicted:
                self._m["pages_evicted"].inc(len(lp) - ns)
        if seq.chain:
            self.prefix_cache.release(seq.chain)
            seq.chain = []
        seq.pages = [[] for _ in range(self.cfg.n_layers)]

    def _preempt_one(self) -> None:
        cands = [s for s in self.seqs.values()
                 if any(s.pages[li] for li in range(self.cfg.n_layers))]
        if not cands:
            raise F.PoolExhaustedError(
                f"pool exhausted with nothing evictable "
                f"({self.n_pool_pages - 1} pages, {len(self.free)} free)")
        victim = min(cands, key=self._seq_value)
        if self.obs is not None:
            rb = self._seq_reclaimable_bytes(victim)
            self.obs.audit.record(
                "camp_preempt", sid=victim.sid,
                value=self._seq_value(victim), reclaimable_bytes=rb,
                pow2_bucket=_pow2_bucket(max(rb, 1)),
                tokens=len(victim.tokens), pins=len(victim.chain),
                candidates=len(cands))
        # verify the victim's pages *before* dropping them: a preemption
        # requeue absorbs generated tokens into the prompt, so corrupted-
        # influenced tokens must be flagged here or they would silently
        # survive the recompute (only costs a dispatch when faults can
        # actually occur)
        if self.integrity and self.faults is not None \
                and not F.verify_seq(self, victim.sid):
            self._m["integrity_failures"].inc()
        self._drop_seq_pages(victim, count_evicted=True)
        victim.tail_len = 0
        victim.preempted = True
        self._pt_dirty = True
        self._m["preemptions"].inc()

    def _record_publish(self, seq: Sequence, pids: list[int],
                        nbytes: np.ndarray, csums: np.ndarray,
                        tags: np.ndarray,
                        msizes: np.ndarray | None = None) -> None:
        """Attach freshly published pages (one per layer) to a sequence.

        ``msizes`` [n_members, L] carries each member codec's would-be
        byte count per page (observatory-on adaptive publishes only).
        """
        raw = self.page_raw_bytes()
        for li, pid in enumerate(pids):
            nb = int(nbytes[li])
            tag = int(tags[li])
            self.page_bytes[pid] = nb
            self.page_checksum[pid] = csums[li]
            self.page_codec_id[pid] = tag
            seq.pages[li].append(pid)
            # per-codec page-tag distribution + per-page ratio histogram
            # (the adaptive composite's member mix shows up here)
            pages_c, bytes_c, h_bytes, h_ratio = self._publish_metrics(tag)
            pages_c.inc()
            bytes_c.inc(nb)
            h_bytes.observe(nb)
            h_ratio.observe(raw / max(nb, 1))
            if self.obs is not None:
                name = (self._tag_names[tag] if tag < len(self._tag_names)
                        else str(tag))
                wb = (None if msizes is None else
                      {self._tag_names[k]: int(msizes[k][li])
                       for k in range(msizes.shape[0])})
                self.obs.on_publish(pid, nb, name, wb)
        self._m["pages_compressed"].inc(len(pids))
        self._m["bytes_raw"].inc(raw * len(pids))
        self._m["bytes_compressed"].inc(int(nbytes.sum()))
        rb = self.request_bytes.setdefault(seq.sid, [0, 0])
        rb[0] += raw * len(pids)
        rb[1] += int(nbytes.sum())
        self._pt_dirty = True

    # -- page table ----------------------------------------------------------

    def _page_table(self) -> jax.Array:
        """Padded device page table [L, S, PMAX] (rebuilt when dirty)."""
        need = max((len(s.pages[0]) for s in self.seqs.values()), default=0)
        while self._pmax < need:
            self._pmax *= 2
            self._pt_dirty = True
        if self._pt_dirty or self._pt_dev is None:
            with self.telemetry.tracer.span("engine.page_table"):
                lyr = self.cfg.n_layers
                pt = np.zeros((lyr, self.max_batch, self._pmax), np.int32)
                for s in self.seqs.values():
                    for li in range(lyr):
                        ids = s.pages[li]
                        pt[li, s.slot, :len(ids)] = ids
                self._pt_dev = jnp.asarray(pt)
                self._pt_dirty = False
        return self._pt_dev

    # -- request lifecycle -----------------------------------------------------

    def release(self, sid: int) -> None:
        """Retire a request: free its private pool pages, unpin its shared
        prefix chain (those pages stay cache-retained for the next request
        that shares the prefix), and recycle its slot."""
        seq = self.seqs.pop(sid)
        # a live cohort member cannot be released mid-prefill (its scratch
        # row would keep publishing pages nobody owns); preempted members
        # are fine — their publishes are already dropped
        assert not (seq.prefilling and not seq.preempted), \
            f"sid {sid} is mid-prefill; cannot release"
        if (self.tier is not None and self.cache_decode_pages
                and not seq.preempted and not seq.corrupted):
            # opt-in: decode-produced pages become demotable too (the
            # multi-turn chat hit path); the tier holds copies, so the
            # pool pages still free normally below
            self._demote_decode_pages(seq)
        self._drop_seq_pages(seq, count_evicted=False)
        if self.prefix_cache is not None:
            # reclaim quarantined entries the moment their last pin drops
            purged = self.prefix_cache.purge_corrupt()
            self.free.extend(purged)
            if self.obs is not None:
                self.obs.on_release(purged)
        if self.obs is not None:
            self.obs.on_retire(sid)
        self._free_slots.append(seq.slot)
        self._pt_dirty = True

    def abort(self, sid: int) -> None:
        """Abandon a request mid-flight (deadline miss, integrity
        restart): drop its pages and mark it preempted so ``release``
        accepts it even mid-prefill — its cohort row keeps computing
        masked garbage that is never published, exactly like a CAMP
        preemption victim (but without the preemption accounting)."""
        seq = self.seqs[sid]
        if seq.preempted:
            return
        self._drop_seq_pages(seq, count_evicted=False)
        seq.tail_len = 0
        seq.preempted = True
        self._pt_dirty = True
        self._maybe_drop_cohort()

    # -- memory tier (serving/tier.py) --------------------------------------

    def attach_tier(self, tier: "T.TieredPageStore") -> None:
        """Attach a host/disk demotion tier: SIP eviction victims demote
        into it (compressed bytes, codec tags and publish-time checksums
        intact) and warm lookups that miss the device pool promote back
        out of it through the prefix-cache publish bookkeeping."""
        assert self.prefix_cache is not None, \
            "a tier needs a prefix cache to demote from"
        assert tier.page == self.page \
            and tier.n_layers == self.cfg.n_layers \
            and tier.codec_name == self.codec.name, \
            "tier layout disagrees with the engine"
        self.tier = tier
        if tier.telemetry is None:
            tier.telemetry = self.telemetry
        if tier.observatory is None:
            tier.observatory = self.obs
        self._h_promote = self.telemetry.registry.histogram(
            "tier_promotion_seconds",
            "wall time to promote a warm chain from the tier",
            codec=self.codec.name)
        self.prefix_cache.demote_cb = self._demote_entry

    def _entry_parent_digest(self, e) -> str:
        """Digest of the token prefix *before* entry ``e``: walk the
        resident ancestor chain (eviction is leaf-first, so ancestors
        are still in the cache when the demotion hook fires)."""
        anc = []
        pid = e.parent
        while pid:
            pe = self.prefix_cache.entries[pid]
            anc.append(pe)
            pid = pe.parent
        digest = T.ROOT
        for pe in reversed(anc):
            digest = T.child_digest(digest, pe.toks)
        return digest

    def _demote_pages(self, parent: str, toks: tuple[int, ...],
                      pids: list[int], *, hits: int = 0,
                      source: str = "prompt") -> None:
        """Gather one page boundary's pool pages and hand them to the
        tier with their publish metadata (one device sync per demotion
        — demotion is off the admission/decode latency path)."""
        got = _gather_entry_pages(self.pools, jnp.asarray(pids, jnp.int32))
        with self.telemetry.tracer.span("engine.sync"):
            leaves = [np.asarray(lf)
                      for lf in jax.device_get(jax.tree.leaves(got))]
        self.tier.demote(parent, toks, leaves,
                         [int(self.page_bytes[p]) for p in pids],
                         [int(self.page_codec_id[p]) for p in pids],
                         [int(self.page_checksum[p]) for p in pids],
                         hits=hits, source=source)

    def _demote_entry(self, e) -> None:
        """Prefix-cache demotion hook (``PrefixCache.demote_cb``):
        capture an eviction victim's compressed pages before they are
        dropped.  Bytes corrupted since publish travel with their
        original checksum, so promotion quarantines them — the tier
        never turns silent pool corruption into served tokens."""
        self._demote_pages(self._entry_parent_digest(e), e.toks,
                           list(e.pages), hits=e.hits)

    def _demote_decode_pages(self, seq: Sequence) -> None:
        """Opt-in retirement hook (``cache_decode_pages``): register the
        sequence's private full pages — decode-produced and any shed
        prompt pages — keyed by the token prefix they cover, so a
        follow-up conversation turn that replays this exchange promotes
        instead of recomputing."""
        page, lyr = self.page, self.cfg.n_layers
        ns = len(seq.chain)
        digest = T.ROOT
        for blk in range(len(seq.pages[0])):
            toks = tuple(seq.tokens[blk * page:(blk + 1) * page])
            if blk >= ns:
                self._demote_pages(digest, toks,
                                   [seq.pages[li][blk]
                                    for li in range(lyr)],
                                   source="decode")
            digest = T.child_digest(digest, toks)

    def _promote_from_tier(self, prompt: list[int], start: int,
                           chain: list[int]) -> tuple[int, list[int]]:
        """Extend a warm hit past the device pool from the tier.

        Walks the tier trie from the first device-uncached block; each
        record is checksum-verified host-side (a corrupt slot is
        quarantined and the walk stops — shorter hit, never bad bytes),
        scattered into freshly reserved pool pages, and re-inserted into
        the prefix cache pinned, exactly like a published prompt page.
        The already-pinned device chain can't be victimized by the
        reservations this makes.  Returns the extended ``(start,
        chain)``.
        """
        tier, cache, page = self.tier, self.prefix_cache, self.page
        lyr = self.cfg.n_layers
        recs = tier.lookup(prompt)
        b = start // page
        if len(recs) <= b:
            return start, chain
        t0 = time.perf_counter()
        stored, promoted = len(prompt) - 1, 0
        # pass 1 (host only): walk the trie, verify checksums, and
        # collect the longest clean run.  read_record returns owned
        # copies, so later evictions/spills cannot alias these leaves.
        picked: list = []
        while (b + len(picked) < len(recs)
               and (b + len(picked) + 1) * page <= stored):
            rec = recs[b + len(picked)]
            lo = (b + len(picked)) * page
            if rec.toks != tuple(prompt[lo:lo + page]):
                break                      # digest collision paranoia
            leaves, ok = tier.read_record(rec)
            if not ok:
                self._m["integrity_failures"].inc()
                break
            picked.append((rec, leaves))
        if not picked:
            return start, chain
        # pass 2: one batched scatter for the whole verified run, rows
        # padded to a power of two aimed at padding page 0
        pids = [self._reserve_pages(lyr) for _ in picked]
        pad = 1 << (len(picked) - 1).bit_length()
        rows = np.asarray(pids + [[0] * lyr] * (pad - len(picked)),
                          np.int32)
        vals = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self.pools),
            [np.stack([lv[i] for _, lv in picked]
                      + [picked[0][1][i]] * (pad - len(picked)))
             for i in range(len(picked[0][1]))])
        self.pools = _promote_scatter(self.pools, vals, jnp.asarray(rows))
        # pass 3 (host only): page-table metadata + cache inserts, in
        # chain order so pin/dedup semantics match a published prompt
        for idx, ((rec, _), bpids) in enumerate(zip(picked, pids)):
            for li, pid in enumerate(bpids):
                self.page_bytes[pid] = rec.nbytes[li]
                self.page_checksum[pid] = rec.checksums[li]
                self.page_codec_id[pid] = rec.codec_ids[li]
            eid, created = cache.insert(
                chain[-1] if chain else 0, rec.toks, bpids,
                sum(rec.nbytes), codec_ids=list(rec.codec_ids))
            displaced = cache.drain_displaced()   # healed-over pages
            self.free.extend(displaced)
            if self.obs is not None:
                self.obs.on_release(displaced)
            if eid is None:        # pinned corrupt twin: cannot map
                for later in pids[idx:]:   # scattered but unmapped —
                    self.free.extend(later)   # contents are harmless
                break
            if not created:        # clean twin already resident: share it
                self.free.extend(bpids)
            cache.pin([eid])
            chain.append(eid)
            tier.on_promoted(rec)
            promoted += 1
            b += 1
        if promoted:
            self._pt_dirty = True
            if self._h_promote is not None:
                self._h_promote.observe(time.perf_counter() - t0)
            if self.telemetry.tracer.enabled:
                self.telemetry.tracer.event(-1, "tier_promote",
                                            blocks=promoted)
        return b * page, chain

    def recycle_device_pool(self) -> int:
        """Drop every retained prefix entry (demoting through the tier
        when one is attached), returning their pages to the free list —
        the multi-turn chat scenario's between-turns device-pool reset.
        Requires an idle engine (no live sequences, no cohort).  Returns
        the number of pages freed."""
        assert not self.seqs and self._cohort is None, \
            "recycle_device_pool with work in flight"
        before = len(self.free)
        while (self.prefix_cache is not None
               and self.prefix_cache.entries
               and self._evict_prefix_pages(self.n_pool_pages)):
            pass
        return len(self.free) - before

    # -- integrity / invariants ---------------------------------------------

    def verify_seq(self, sid: int) -> bool:
        """Recompute checksums for every pool page the sequence maps;
        quarantines corrupt shared entries.  See serving/faults.py."""
        return F.verify_seq(self, sid)

    def debug_validate(self) -> None:
        """Assert page/refcount/slot accounting is exact (test teardowns
        and chaos drains).  See :func:`repro.serving.faults.debug_validate`."""
        F.debug_validate(self)

    def add_request(self, sid: int, prompt: list[int]) -> None:
        self.add_requests({sid: prompt})

    def add_requests(self, prompts: dict[int, list[int]]
                     ) -> dict[int, int]:
        """Admit a batch of prompts and prefill them to completion.

        Blocking convenience wrapper over the cohort machinery: admits all
        prompts as one cohort and drains it with full-width chunks.  The
        continuous-batching scheduler instead drives the same cohort one
        budgeted chunk per iteration via :meth:`mixed_step`.  Returns
        ``begin_cohort``'s ``{sid: cached_tokens}`` warm-hit map.
        """
        cached = self.begin_cohort(prompts)
        while self._cohort is not None:
            self.mixed_step(decode_sids=[], pf_tokens=self.prefill_chunk)
        return cached

    def begin_cohort(self, prompts: dict[int, list[int]]
                     ) -> dict[int, int]:
        """Admit prompts into a chunked-prefill cohort without running it.

        With a prefix cache attached, each prompt's longest cached
        page-boundary prefix is looked up, pinned, and mapped into the
        new sequence's page table; the member starts chunked prefill at
        the first uncached boundary (full hits skip prefill entirely and
        are decodable immediately).  Returns ``{sid: cached_tokens}``.

        Allocates batch slots and the cohort's exact-K/V scratch; no
        model compute happens until :meth:`mixed_step` is called with a
        nonzero ``pf_tokens``.  All cohort members share one *relative*
        chunk grid from their per-row start offsets, which keeps the
        mixed dispatch's shapes static; requests arriving while a cohort
        is in flight wait for the next cohort.
        """
        with self.telemetry.tracer.span("engine.begin_cohort"):
            return self._begin_cohort(prompts)

    def _begin_cohort(self, prompts: dict[int, list[int]]
                      ) -> dict[int, int]:
        # a cohort whose live members all finished (the rest preempted)
        # may still be nominally in flight; clear it before validating
        self._maybe_drop_cohort()
        # validate the whole batch before mutating any engine state, so a
        # rejected admission leaves no half-admitted sequences behind
        assert self._cohort is None, "a prefill cohort is already in flight"
        assert len(prompts) <= len(self._free_slots), \
            "engine at max_batch capacity"
        for sid, prompt in prompts.items():
            assert sid not in self.seqs, sid
            assert prompt, f"empty prompt for sid {sid}"
        cached: dict[int, int] = {}
        if not prompts:
            return cached
        cfg, chunk, page = self.cfg, self.prefill_chunk, self.page
        lyr, kvh, dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        tracer = self.telemetry.tracer
        seqs, starts = [], []
        for sid, prompt in prompts.items():
            start, chain = 0, []
            if self.prefix_cache is not None:
                with tracer.span("prefix_cache.lookup"):
                    start, chain = self.prefix_cache.lookup(prompt)
                if self.integrity:
                    # warm-hit trust boundary: verify the chain's pool
                    # pages before mapping them; a corrupt entry
                    # truncates the hit (the request recomputes from
                    # there, never serving bad bytes)
                    with tracer.span("engine.verify"):
                        vstart, chain = F.verified_prefix(self, start,
                                                          chain)
                    if vstart != start:
                        self._m["integrity_failures"].inc()
                        if tracer.enabled:
                            tracer.event(sid, "hit_truncated", hit=start,
                                         verified=vstart)
                        start = vstart
                self.prefix_cache.pin(chain)
                if self.tier is not None:
                    # the device chain is pinned first, so the pool
                    # reservations promotion makes can never victimize
                    # the chain being extended
                    start, chain = self._promote_from_tier(prompt, start,
                                                           chain)
            ent = [self.prefix_cache.entries[e] for e in chain]
            seq = Sequence(sid=sid, slot=self._free_slots.pop(),
                           tokens=list(prompt),
                           pages=[[e.pages[li] for e in ent]
                                  for li in range(lyr)],
                           chain=list(chain), prefilling=True)
            self.seqs[sid] = seq
            cached[sid] = start
            if self.obs is not None:
                # counterfactual access stream: one key per full prompt
                # block regardless of the real lookup outcome; the warm
                # chain's pages score real reuse accesses
                self.obs.on_admit(sid, prompt, (len(prompt) - 1) // page,
                                  [pid for e in ent for pid in e.pages])
            if start >= len(prompt) - 1:
                # full prefix hit: every stored token is already paged in
                # — no prefill work, straight to decode (tail is empty:
                # a full hit implies the stored length is page-aligned)
                seq.prefilling = False
                continue
            seqs.append(seq)
            starts.append(start)
        self._pt_dirty = True
        if not seqs:
            return cached
        # the grid covers *stored* positions only (prompt minus the last
        # token, whose K/V the first decode step computes into the tail)
        maxstored = max(len(s.tokens) - 1 for s in seqs)
        maxrel = max(len(s.tokens) - 1 - st for s, st in zip(seqs, starts))
        # scratch length: one chunk of headroom past the longest stored
        # prefix so a budget-split (non-chunk-aligned) offset never pushes
        # the static-width scratch write out of bounds, rounded up to a
        # power-of-two chunk count so retraces stay logarithmic
        n_chunks = -(-maxstored // chunk) + 1
        cap = 1
        while cap < n_chunks:
            cap *= 2
        tmax = cap * chunk
        # scratch rows cover only the admitted prompts (rounded up to a
        # power of two, capped at max_batch) — admission cost scales with
        # the cohort actually admitted, not engine capacity; ``row`` maps
        # each sequence to its scratch row, distinct from its decode slot
        nrows = 1
        while nrows < len(seqs):
            nrows *= 2
        nrows = min(nrows, self.max_batch)
        row = {s.sid: r for r, s in enumerate(seqs)}
        toks = np.zeros((nrows, tmax), np.int32)
        for s in seqs:
            toks[row[s.sid], :len(s.tokens)] = s.tokens
        kscr = jnp.zeros((lyr, nrows, tmax, kvh, dh), jnp.float32)
        vscr = jnp.zeros_like(kscr)
        # lossless codecs never read the canonical view (prefill attends
        # the exact scratch directly), so it shrinks to zero length — no
        # doubled scratch memory for codecs whose roundtrip is free
        can_t = 0 if self.codec.lossless else tmax
        kcan = jnp.zeros((lyr, nrows, can_t, kvh, dh), jnp.float32)
        vcan = jnp.zeros_like(kcan)
        if any(starts):
            # dequantize each warm row's cached chain into its scratch
            # prefix region (canonical by construction); WP rounds up to
            # a power of two so retraces stay logarithmic, capped at the
            # scratch's page count (starts <= maxstored < tmax, so the
            # cap never cuts below the deepest chain — without it a
            # non-power-of-two prefill_chunk/page ratio could push the
            # fill block past the scratch length)
            wp = 1
            while wp < max(starts) // page:
                wp *= 2
            wp = min(wp, tmax // page)
            wpt = np.zeros((lyr, nrows, wp), np.int32)
            wlen = np.zeros(nrows, np.int32)
            for s, st in zip(seqs, starts):
                r = row[s.sid]
                wlen[r] = st
                for li in range(lyr):
                    wpt[li, r, :st // page] = s.pages[li][:st // page]
            kscr, vscr, kcan, vcan = _fill_warm_scratch(
                kscr, vscr, kcan, vcan, self.pools, jnp.asarray(wpt),
                jnp.asarray(wlen), codec=self.codec)
        self._cohort = _Cohort(seqs=seqs, row=row, toks=toks, kscr=kscr,
                               vscr=vscr, kcan=kcan, vcan=vcan,
                               starts=starts, maxrel=maxrel,
                               pub=[st // page for st in starts],
                               done_sids=set())
        return cached

    def _maybe_drop_cohort(self) -> None:
        """Retire the cohort early when no live member still needs it.

        A CAMP-preempted member never completes its grid (its publishes
        are dropped), so a cohort whose only unfinished members are
        preempted would otherwise stay in flight forever and block the
        next admission.
        """
        co = self._cohort
        if co is not None and all(s.sid in co.done_sids or s.preempted
                                  for s in co.seqs):
            for s in co.seqs:
                s.prefilling = False
            self._cohort = None

    def _advance_cohort(self, n: int) -> list[int]:
        """Post-dispatch cohort bookkeeping for an ``n``-token advance.

        Publishes every page the chunk completed (CAMP accounting rides
        on the same batched publish path decode uses; prompt pages also
        register in the prefix cache), writes the final partial page of
        members whose prefill just finished into their decode tail slots,
        and retires the cohort when the relative grid drains.  Returns
        the sids whose prefill completed this step.
        """
        co, page = self._cohort, self.page
        new_roff = min(co.roff + n, co.maxrel)
        entries = []
        for i, s in enumerate(co.seqs):
            stored = len(s.tokens) - 1
            upto = min(co.starts[i] + new_roff, stored) // page
            entries.extend((s, blk) for blk in range(co.pub[i], upto))
            co.pub[i] = max(co.pub[i], upto)
        if entries:
            with self.telemetry.tracer.span("engine.publish"):
                rows = jnp.asarray([co.row[s.sid] for s, _ in entries],
                                   jnp.int32)
                blks = jnp.asarray([b for _, b in entries], jnp.int32)
                kb, vb = _gather_prefill_blocks(co.kscr, co.vscr, rows,
                                                blks, page=page)
                self._publish(kb, vb, [s for s, _ in entries],
                              blocks=[b for _, b in entries])
        completed, tails = [], []
        for i, s in enumerate(co.seqs):
            stored = len(s.tokens) - 1
            if s.sid in co.done_sids or co.starts[i] + new_roff < stored:
                continue
            co.done_sids.add(s.sid)
            s.prefilling = False
            # final partial page -> decode tail buffers (exact f32, like
            # the pool pages sourced from the same scratch); the first
            # decode step appends the last prompt token's K/V here
            s.tail_len = 0 if s.preempted else stored % page
            if s.tail_len:
                tails.append((s, stored // page))
            completed.append(s.sid)
        if tails:
            rows = jnp.asarray([co.row[s.sid] for s, _ in tails], jnp.int32)
            slots = jnp.asarray([s.slot for s, _ in tails], jnp.int32)
            blks = jnp.asarray([b for _, b in tails], jnp.int32)
            self.tail_k, self.tail_v = _write_tails(
                self.tail_k, self.tail_v, co.kscr, co.vscr, rows, slots,
                blks, page=page)
        co.roff = new_roff
        if new_roff >= co.maxrel:
            self._cohort = None
        return completed

    def _publish(self, k_blocks, v_blocks, seqs: list[Sequence],
                 blocks: list[int] | None = None) -> None:
        """Publish len(seqs) filled pages per layer in one dispatch.

        Blocks are layer-major: [L * len(seqs), K, page, D] with the
        sequence order of ``seqs`` repeating inside each layer group.
        A sequence may appear several times (one entry per page).

        ``blocks[j]`` carries the absolute page index of entry ``j`` for
        *prompt* publishes: those pages register in the prefix cache
        (pinned by the publisher) so later requests can share them.  Two
        same-prefix prompts in one cohort dedup here — the second
        publisher's fresh pages go back to the free list and its page
        table maps the first publisher's entry instead (the bits are
        identical by the canonical-prefix contract).  Decode tail
        publishes pass ``blocks=None`` and stay private.

        CAMP quirk fix (shared with the reference): pages owned by a
        sequence that is already preempted — or that becomes the victim
        of this very reservation — are not attached; they go straight
        back to the free list instead of leaking until ``release``.
        """
        lyr, m_all = self.cfg.n_layers, len(seqs)
        keep = [j for j, s in enumerate(seqs) if not s.preempted]
        if not keep:
            return
        if len(keep) != m_all:
            sel = jnp.asarray([li * m_all + j
                               for li in range(lyr) for j in keep])
            k_blocks, v_blocks = k_blocks[sel], v_blocks[sel]
            seqs = [seqs[j] for j in keep]
            if blocks is not None:
                blocks = [blocks[j] for j in keep]
        m = len(seqs)
        pids = self._reserve_pages(lyr * m)
        # observatory + composite codec: also pull every member's
        # would-be page size out of the same dispatch (what-if sampling)
        want_members = (self.obs is not None
                        and getattr(self.codec, "members", None)
                        is not None)
        self.pools, nbytes, csums, tags, msizes = _publish_blocks(
            self.pools, k_blocks, v_blocks,
            jnp.asarray(pids, jnp.int32), codec=self.codec,
            use_fused=self.use_fused_fill, member_sizes=want_members)
        with self.telemetry.tracer.span("engine.sync"):  # 1 per publish
            nbytes, csums, tags, msizes = jax.device_get(
                (nbytes, csums, tags, msizes))
        nbytes, csums = np.asarray(nbytes), np.asarray(csums)
        tags = np.asarray(tags)
        if msizes is not None:
            msizes = np.asarray(msizes)
        for j, seq in enumerate(seqs):
            if seq.preempted:      # victim of our own reservation
                self.free.extend(pids[j::m])
                continue
            self._record_publish(seq, pids[j::m], nbytes[j::m], csums[j::m],
                                 tags[j::m],
                                 None if msizes is None else msizes[:, j::m])
            if blocks is not None and self.prefix_cache is not None:
                self._register_prompt_page(seq, blocks[j], pids[j::m],
                                           int(nbytes[j::m].sum()))
        if self.faults is not None:
            # fault-injection hook: corruption lands in the compressed
            # pool bytes *after* checksums were recorded, exactly like
            # post-publish bit rot
            for j, seq in enumerate(seqs):
                if not seq.preempted:
                    for li, pid in enumerate(pids[j::m]):
                        self.faults.page_published(self, li, pid)

    def _register_prompt_page(self, seq: Sequence, blk: int,
                              pids: list[int], nbytes: int) -> None:
        """Attach a freshly published prompt page to the prefix cache."""
        page, cache = self.page, self.prefix_cache
        if self.shed_cache_inserts or blk != len(seq.chain):
            # degradation-ladder level 1: skip speculative insertions
            # under pool pressure (the page stays private).  Once one
            # block is shed the sequence's chain is broken, so later
            # blocks must stay private too (blk != len(chain)) even
            # after pressure clears.
            self._m["shed_inserts"].inc()
            return
        assert blk == len(seq.chain), (blk, len(seq.chain))
        parent = seq.chain[-1] if seq.chain else 0
        toks = tuple(seq.tokens[blk * page:(blk + 1) * page])
        eid, created = cache.insert(
            parent, toks, pids, nbytes,
            codec_ids=[int(self.page_codec_id[p]) for p in pids])
        displaced = cache.drain_displaced()         # healed-over pages
        self.free.extend(displaced)
        if self.obs is not None:
            self.obs.on_release(displaced)
        if displaced and self.telemetry.tracer.enabled:
            self.telemetry.tracer.event(seq.sid, "cache_heal",
                                        pages=len(displaced))
        if eid is None:            # pinned corrupt twin: block stays private
            self._m["shed_inserts"].inc()
            return
        cache.pin([eid])
        seq.chain.append(eid)
        if created:
            if self.obs is not None:
                self.obs.on_cache_insert(seq.sid, blk, nbytes)
        else:                      # in-cohort dedup: map the shared pages
            ent = cache.entries[eid]
            for li in range(self.cfg.n_layers):
                assert seq.pages[li][blk] == pids[li]
                seq.pages[li][blk] = ent.pages[li]
            self.free.extend(pids)
            if self.obs is not None:
                self.obs.on_dedup(seq.sid, blk, nbytes, pids, ent.pages)
            self._pt_dirty = True
            # the duplicate never lands in the pool: reverse its
            # _record_publish accounting so compression stats count each
            # resident page once (mirrored in the reference oracle)
            lyr = self.cfg.n_layers
            self._m["pages_compressed"].inc(-lyr)
            self._m["bytes_raw"].inc(-self.page_raw_bytes() * lyr)
            self._m["bytes_compressed"].inc(-nbytes)

    # -- decode ------------------------------------------------------------------

    def decode_batch(self, sids: list[int] | None = None) -> dict[int, int]:
        """Greedy-decode one token for every active (or given) sequence."""
        out, _ = self.mixed_step(decode_sids=sids, pf_tokens=0)
        return out

    def mixed_step(self, decode_sids: list[int] | None = None,
                   pf_tokens: int = 0) -> tuple[dict[int, int], list[int]]:
        """One continuous-batching iteration.

        Advances every given (default: every decodable) sequence one
        decode token AND the in-flight prefill cohort by up to
        ``pf_tokens`` prompt tokens (clamped to ``prefill_chunk``, one
        dispatch's static width) — through a single jitted dispatch
        (:func:`_mixed_step`) when both halves are present, or the
        decode-only / prefill-only dispatch otherwise.  ``pf_tokens``
        below ``prefill_chunk`` is a budget-split chunk: the dispatch
        width stays static, tokens past the split are masked padding.

        Returns ``(decoded {sid: next_token}, completed_prefill_sids)``.
        """
        if decode_sids is None:
            decode_sids = [s.sid for s in self.seqs.values()
                           if not (s.preempted or s.done or s.prefilling)]
        sids = [sid for sid in dict.fromkeys(decode_sids)  # dedup in order
                if not (self.seqs[sid].preempted or self.seqs[sid].done
                        or self.seqs[sid].prefilling)]
        co = self._cohort
        # one dispatch advances at most one chunk (the static width of the
        # prefill half); larger pf_tokens would silently skip tokens
        n = 0 if co is None else max(0, min(pf_tokens, self.prefill_chunk,
                                            co.maxrel - co.roff))
        tracer = self.telemetry.tracer
        with tracer.span("engine.prepare"):
            if n > 0:
                c = self.prefill_chunk
                nrows, tmax = co.toks.shape
                ptoks_h = np.zeros((nrows, c), np.int32)
                offs_h = np.zeros(nrows, np.int32)
                for i, s in enumerate(co.seqs):
                    r = co.row[s.sid]
                    # per-row absolute chunk start; clamped so the static-
                    # width scratch write stays in bounds for rows already
                    # past their stored length (their writes are garbage
                    # the grid never publishes or attends)
                    off = min(co.starts[i] + co.roff, tmax - c)
                    offs_h[r] = off
                    ptoks_h[r] = co.toks[r, off:off + c]
                # budget-split chunk: tokens past the valid width are zero
                # padding — their scratch writes land beyond off+n and are
                # rewritten by the next chunk before any valid query
                # (always at a position < its own write offset) can
                # attend them
                ptoks_h[:, n:] = 0
                ptoks = jnp.asarray(ptoks_h)
                offs_d = jnp.asarray(offs_h)
            if sids:
                pt = self._page_table()
                page_cnt, last_tok, pos, tail_len, active = \
                    self._decode_inputs(sids)
        if sids:
            with tracer.span("engine.dispatch"):
                if n > 0:
                    (nxt, self.tail_k, self.tail_v, co.kscr, co.vscr,
                     co.kcan, co.vcan) = _mixed_step(
                        self.params, self.pools, self.tail_k, self.tail_v,
                        co.kscr, co.vscr, co.kcan, co.vcan, pt, page_cnt,
                        last_tok, pos, tail_len, active, ptoks, offs_d,
                        cfg=self.cfg, page=self.page, codec=self.codec,
                        use_fused=self.use_fused)
                else:
                    nxt, self.tail_k, self.tail_v = _decode_step(
                        self.params, self.pools, self.tail_k, self.tail_v,
                        pt, page_cnt, last_tok, pos, tail_len, active,
                        cfg=self.cfg, codec=self.codec,
                        use_fused=self.use_fused)
            with tracer.span("engine.sync"):          # 1 sync / step
                nxt = np.asarray(nxt)
            out = self._decode_post(sids, nxt)
        else:
            out = {}
            if n > 0:
                with tracer.span("engine.dispatch"):
                    co.kscr, co.vscr, co.kcan, co.vcan = _prefill_chunk(
                        self.params, ptoks, co.kscr, co.vscr, co.kcan,
                        co.vcan, offs_d, cfg=self.cfg, page=self.page,
                        codec=self.codec)
        # decode tail publishes land first (inside _decode_post), then the
        # chunk's completed prefill pages — the reference oracle replays
        # the same iteration order
        completed = []
        if n > 0:
            with tracer.span("engine.cohort_advance"):
                completed = self._advance_cohort(n)
        # a decode-side publish may have preempted the cohort's last live
        # member this very step; don't leave a dead cohort in flight
        self._maybe_drop_cohort()
        return out, completed

    def _decode_inputs(self, sids: list[int]):
        """Pack the padded per-slot decode state for a dispatch, and count
        the pages its attention walks (``_WALK_KEYS``) against the
        padded table of the current ``PMAX``."""
        sb = self.max_batch
        active = np.zeros(sb, bool)
        last_tok = np.zeros(sb, np.int32)
        pos = np.zeros(sb, np.int32)
        tail_len = np.zeros(sb, np.int32)
        page_cnt = np.zeros(sb, np.int32)
        for sid in sids:
            s = self.seqs[sid]
            active[s.slot] = True
            last_tok[s.slot] = s.tokens[-1]
            pos[s.slot] = len(s.tokens) - 1
            tail_len[s.slot] = s.tail_len
            page_cnt[s.slot] = len(s.pages[0])
        lyr = self.cfg.n_layers
        self._m["attn_pages_read"].inc(lyr * int(page_cnt.sum()))
        self._m["attn_pages_padded"].inc(lyr * sb * self._pmax)
        return (jnp.asarray(page_cnt), jnp.asarray(last_tok),
                jnp.asarray(pos), jnp.asarray(tail_len),
                jnp.asarray(active))

    def _decode_post(self, sids: list[int], nxt: np.ndarray
                     ) -> dict[int, int]:
        """Append decoded tokens; publish every tail page that filled."""
        if self.faults is not None:
            nxt = self.faults.garble_tokens(
                nxt, [self.seqs[sid].slot for sid in sids])
        filled: list[Sequence] = []
        out: dict[int, int] = {}
        for sid in sids:
            s = self.seqs[sid]
            out[sid] = int(nxt[s.slot])
            s.tokens.append(out[sid])
            s.tail_len += 1
            if s.tail_len == self.page:
                filled.append(s)
                s.tail_len = 0
        if filled:
            with self.telemetry.tracer.span("engine.publish"):
                slots = jnp.asarray([s.slot for s in filled], jnp.int32)
                kb, vb = _gather_tail_blocks(self.tail_k, self.tail_v,
                                             slots)
                self._publish(kb, vb, filled)
        return out

    def decode_one(self, sid: int) -> int:
        """Greedy-decode one token for sequence sid (compat shim)."""
        out = self.decode_batch([sid])
        if sid not in out:
            seq = self.seqs[sid]                   # KeyError for unknown sid
            state = ("preempted" if seq.preempted
                     else "prefilling" if seq.prefilling else "done")
            raise ValueError(f"sequence {sid} is {state}; cannot decode")
        return out[sid]

    # -- metrics ------------------------------------------------------------------

    def compression_ratio(self) -> float:
        if not self._m["bytes_compressed"].value:
            return 1.0
        return self._m["bytes_raw"].value / self._m["bytes_compressed"].value

    def pool_used_pages(self) -> int:
        return (self.n_pool_pages - 1) - len(self.free)
