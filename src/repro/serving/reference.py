"""Reference serving engine: host-looped, one sequence / layer at a time.

This is the behavioral oracle: ``serving/engine.py`` runs the batched
device-resident hot path and must produce token-for-token identical
greedy output to this implementation (tests/test_serving_batched.py,
tests/test_scheduler.py, tests/test_prefix_cache.py).  It is also the
baseline that ``benchmarks/bench_serve.py`` measures speedups against.
Do not optimize this file — its value is being the slow, obviously
correct path.

The inference-side integration of the thesis pillars:

  * KV pages are stored **compressed** through the same pluggable
    :class:`~repro.codecs.PageCodec` the batched engine runs (default:
    the B+Delta int8 form the fused Pallas decode kernel reads —
    kernels/paged_attention.py);
  * page addressing is **LCP**: fixed target size per page, page table ->
    pool index, one shift to locate a token (no prefix sums);
  * the finite HBM page pool is managed by **CAMP**-style value scoring:
    when the pool is full, retained prefix-cache entries evict first
    (SIP value ranking), then the least-valuable sequence (value =
    reuse-proxy / compressed size, the MVE function) is preempted;
  * completed prompt pages are shared across requests through the
    **prefix cache** (serving/prefix_cache.py): lookup/pin at admission,
    insert at publish, release at retirement — the same protocol the
    batched engine speaks, so warm-cache paths stay token-for-token.

Prefill stores KV for every prompt token but the last; the first decode
step computes the last prompt token's K/V exactly once into the tail
(the historical "duplicated last prompt key" quirk is fixed in both
engines).  Prefill attention follows the canonical-prefix contract: a
query reads the compress-then-dequantize round trip of every completed
earlier page and exact f32 values inside its own page — which makes
published pages pure functions of the token prefix and is what makes
cross-request page sharing sound.  Decode attends [compressed pages +
exact tail], the same rule at tail granularity.

Prefill *numerics* route through the same jitted chunk dispatch the
batched engine uses (``engine._prefill_chunk``, at one scratch row).
This is deliberate, and new with the prefix cache: the canonical
contract feeds quantized page values back into prefill attention, so
any cross-implementation float noise (XLA fuses a jitted graph
differently than op-by-op dispatch) would be amplified through the
int8 quantizer into token divergence.  The dispatch is bit-invariant
to row count, scratch length, chunk width, and grid offsets (pinned by
tests/test_prefix_cache.py), which is exactly the property the oracle
exercises by replaying a different schedule shape.  Everything else —
paging, CAMP accounting, cache pin/insert/release, publishes into a
numpy pool, decode — is independently reimplemented host-side.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import codecs
from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.serving import engine as _E
from repro.serving import faults as F
from repro.serving.prefix_cache import PrefixCache
from repro.serving.telemetry import Telemetry


@dataclass
class Sequence:
    sid: int
    tokens: list[int]
    pages: list[list[int]]               # [L][n_pages] pool ids
    tail_k: np.ndarray                   # [L, page, K, Dh] f32
    tail_v: np.ndarray
    tail_len: int = 0
    done: bool = False
    preempted: bool = False
    corrupted: bool = False              # integrity check failed (faults.py)
    # chunked-prefill oracle state (begin_request / prefill_advance):
    prefilling: bool = False
    pf_start: int = 0                    # prefix-cache hit boundary
    pf_pos: int = 0                      # prompt tokens processed so far
    pf_published: int = 0                # full pages published or mapped
    pf_k: jax.Array | None = None        # [L, 1, Tpad, K, Dh] f32 scratch
    pf_v: jax.Array | None = None
    pf_kc: jax.Array | None = None       # carried canonical view (same
    pf_vc: jax.Array | None = None       # shape; see engine._Cohort)
    # prefix-cache chain (entry ids, block order); pages[li][:len(chain)]
    # are shared, the rest private
    chain: list[int] = field(default_factory=list)


class ReferencePagedKVEngine:
    """Greedy-decoding engine over a dense-GQA transformer (oracle path)."""

    def __init__(self, cfg: ArchConfig, params, *, page_size: int = 16,
                 n_pool_pages: int = 256,
                 prefix_cache: PrefixCache | None = None,
                 prefill_chunk: int | None = None,
                 codec: str | codecs.PageCodec | None = None,
                 faults: "F.FaultInjector | None" = None,
                 integrity: bool = True,
                 telemetry: Telemetry | None = None):
        assert cfg.attn_kind == "gqa" and not cfg.is_encdec
        if prefix_cache is not None:
            assert prefix_cache.page == page_size \
                and prefix_cache.n_layers == cfg.n_layers, \
                "prefix cache shape disagrees with the engine"
        self.cfg = cfg
        self.params = params
        self.page = page_size
        self.n_pool_pages = n_pool_pages
        self.prefix_cache = prefix_cache
        # page codec: same registry singleton as the batched engine, so
        # the shared jitted prefill dispatch reuses one trace
        self.codec = codecs.resolve(codec)
        # dispatch width of the shared jitted prefill step (bit-invariant
        # to the choice; kept as a knob for jit-cache reuse with an
        # engine of a different width)
        self.prefill_chunk = (2 * page_size if prefill_chunk is None
                              else prefill_chunk)
        assert self.prefill_chunk % page_size == 0
        # compressed page pools (the LCP target-size + metadata regions):
        # the codec's pool pytree, held as host numpy leaves
        self.pools = jax.tree.map(          # np.array: writable host copies
            np.array, self.codec.init_pools(n_pool_pages, cfg.n_kv_heads,
                                            page_size, cfg.head_dim))
        self.free: list[int] = list(range(n_pool_pages - 1, 0, -1))
        self.page_bytes = np.zeros(n_pool_pages, np.int64)
        # publish-time integrity checksums (faults.page_checksums),
        # verified at the same trust boundaries as the batched engine
        self.page_checksum = np.zeros(n_pool_pages, np.uint32)
        # per-page codec-id tags, mirroring the batched engine
        self.page_codec_id = np.zeros(n_pool_pages, np.int32)
        self.integrity = integrity
        self.faults = faults
        # degradation-ladder level >= 1 (scheduler-driven): stop inserting
        # new prompt pages into the prefix cache
        self.shed_cache_inserts = False
        self.seqs: dict[int, Sequence] = {}
        # cumulative published bytes per request (mirror of the batched
        # engine's per-request compression report)
        self.request_bytes: dict[int, list[int]] = {}
        # registry-backed counters mirroring the batched engine's exact
        # metric series (same names/labels), so engine-vs-oracle stats
        # equality holds through the `.stats` properties
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        self._init_metrics()
        if faults is not None:
            faults.telemetry = self.telemetry
        if prefix_cache is not None:
            prefix_cache.telemetry = self.telemetry

    # telemetry plumbing is shared with the batched engine by
    # construction — identical attribute contracts (codec, telemetry,
    # free, pool_used_pages), identical metric series
    _STAT_KEYS = _E.PagedKVEngine._STAT_KEYS
    _WALK_KEYS = ()                # one sequence per call: no batched walk
    _init_metrics = _E.PagedKVEngine._init_metrics
    _publish_metrics = _E.PagedKVEngine._publish_metrics
    stats = _E.PagedKVEngine.stats
    load_stats_dict = _E.PagedKVEngine.load_stats_dict
    sample_gauges = _E.PagedKVEngine.sample_gauges

    # -- pool bookkeeping ----------------------------------------------------

    def page_raw_bytes(self) -> int:
        c = self.cfg
        return 2 * self.page * c.n_kv_heads * c.head_dim * 2   # K+V bf16

    def pool_pressure(self) -> float:
        """Non-reclaimable pool fraction in [0, 1] (mirror of the batched
        engine): the degradation ladder's input signal."""
        cap = self.n_pool_pages - 1
        reclaimable = len(self.free)
        if self.prefix_cache is not None:
            reclaimable += self.prefix_cache.retained_pages()
        return max(0.0, 1.0 - reclaimable / cap)

    def _alloc_page(self) -> int:
        """Mirror of the batched engine's reclaim order: free list, then
        retained prefix-cache entries, then CAMP preemption."""
        while not self.free:
            if not self._evict_prefix_pages(1):
                self._preempt_one()
        return self.free.pop()

    def _evict_prefix_pages(self, need: int) -> bool:
        if self.prefix_cache is None:
            return False
        pids = self.prefix_cache.evict_for(need)
        if not pids:
            return False
        self.free.extend(pids)
        self._m["prefix_pages_evicted"].inc(len(pids))
        return True

    def _seq_value(self, seq: Sequence) -> float:
        """CAMP/MVE value: reuse proxy / *reclaimable* compressed size
        (smaller = victim; mirror of the batched engine — shared prefix
        pages count only when this sequence is their sole pinner)."""
        if seq.done:
            return -1.0
        ns = len(seq.chain)
        size = sum(int(self.page_bytes[p])
                   for lp in seq.pages for p in lp[ns:])
        for eid in seq.chain:
            e = self.prefix_cache.entries[eid]
            if e.refcount == 1:
                size += e.nbytes
        return (len(seq.tokens) + 1) / max(size, 1)

    def _drop_seq_pages(self, seq: Sequence, *, count_evicted: bool) -> None:
        ns = len(seq.chain)
        for lp in seq.pages:
            self.free.extend(lp[ns:])
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
        # verify the victim's pages *before* dropping them: a preemption
        # requeue folds already-decoded tokens into the prompt, and a
        # corrupted page must not influence tokens the absorb path keeps
        if self.integrity and self.faults is not None \
                and not F.verify_seq(self, victim.sid):
            self._m["integrity_failures"].inc()
        self._drop_seq_pages(victim, count_evicted=True)
        victim.tail_len = 0
        victim.preempted = True
        self._m["preemptions"].inc()

    def _publish_page(self, seq: Sequence, li: int,
                      k_blk: np.ndarray, v_blk: np.ndarray) -> None:
        """Compress one full [page, K, Dh] block into the pool.

        CAMP quirk fix (shared with the batched engine): a preempted
        sequence's publishes are dropped — including the in-flight
        publish whose own allocation picked it as the victim — instead
        of re-attaching fresh pages that would leak until ``release``.
        """
        if seq.preempted:
            return
        pid = self._alloc_page()
        if seq.preempted:          # victim of its own allocation just now
            self.free.append(pid)
            return
        kk = jnp.swapaxes(jnp.asarray(k_blk)[None], 1, 2)   # [1, K, page, Dh]
        vv = jnp.swapaxes(jnp.asarray(v_blk)[None], 1, 2)
        pg = self.codec.compress_kv_pages(kk, vv)
        for pool, new in zip(jax.tree.leaves(self.pools),
                             jax.tree.leaves(pg)):
            pool[pid] = np.asarray(new[0])
        # same byte-accounting function as the batched engine's device
        # path, so CAMP values and stats match bit-for-bit on prompt
        # pages (shared prefill dispatch) — decode-tail pages are only
        # token-pinned across engines, so codecs whose sizes read exact
        # bits (ulp_stable_sizes=False) may differ by a few bytes there
        nbytes = int(np.asarray(self.codec.page_nbytes(pg))[0])
        self.page_bytes[pid] = nbytes
        # publish-time checksum: same jitted function the batched engine
        # runs inside its publish dispatch, on the same compressed bits
        self.page_checksum[pid] = np.asarray(F._checksum_jit(pg))[0]
        self.page_codec_id[pid] = int(np.asarray(self.codec.page_tags(pg))[0])
        seq.pages[li].append(pid)
        tag = int(self.page_codec_id[pid])
        pages_c, bytes_c, h_bytes, h_ratio = self._publish_metrics(tag)
        pages_c.inc()
        bytes_c.inc(nbytes)
        h_bytes.observe(nbytes)
        h_ratio.observe(self.page_raw_bytes() / max(nbytes, 1))
        self._m["pages_compressed"].inc()
        self._m["bytes_raw"].inc(self.page_raw_bytes())
        self._m["bytes_compressed"].inc(nbytes)
        rb = self.request_bytes.setdefault(seq.sid, [0, 0])
        rb[0] += self.page_raw_bytes()
        rb[1] += nbytes
        if self.faults is not None:
            self.faults.page_published(self, li, pid)

    def _publish_block(self, seq: Sequence, k_blk: np.ndarray,
                       v_blk: np.ndarray, blk: int | None = None) -> None:
        """Publish one block across all layers; register prompt pages
        (``blk`` = absolute page index) in the prefix cache, deduping
        against an already-resident identical page."""
        for li in range(self.cfg.n_layers):
            self._publish_page(seq, li, k_blk[li], v_blk[li])
        if blk is None or seq.preempted or self.prefix_cache is None:
            return
        if self.shed_cache_inserts or blk != len(seq.chain):
            # degradation-ladder shed, or the chain already broke on an
            # earlier shed block — later blocks stay private (a chain
            # entry's position must equal its block index)
            self._m["shed_inserts"].inc()
            return
        page, cache, lyr = self.page, self.prefix_cache, self.cfg.n_layers
        parent = seq.chain[-1] if seq.chain else 0
        toks = tuple(seq.tokens[blk * page:(blk + 1) * page])
        pids = [seq.pages[li][blk] for li in range(lyr)]
        nbytes = sum(int(self.page_bytes[p]) for p in pids)
        eid, created = cache.insert(
            parent, toks, pids, nbytes,
            codec_ids=[int(self.page_codec_id[p]) for p in pids])
        self.free.extend(cache.drain_displaced())   # healed-over pages
        if eid is None:            # pinned corrupt twin: block stays private
            self._m["shed_inserts"].inc()
            return
        cache.pin([eid])
        seq.chain.append(eid)
        if not created:            # dedup: map the shared pages instead
            ent = cache.entries[eid]
            for li in range(lyr):
                self.free.append(seq.pages[li][blk])
                seq.pages[li][blk] = ent.pages[li]
            # reverse the duplicate's publish accounting (mirror of the
            # batched engine): stats count each resident page once
            self._m["pages_compressed"].inc(-lyr)
            self._m["bytes_raw"].inc(-self.page_raw_bytes() * lyr)
            self._m["bytes_compressed"].inc(-nbytes)

    # -- request lifecycle -----------------------------------------------------

    def add_requests(self, prompts: dict[int, list[int]]) -> None:
        """API parity with the batched engine: sequential admission (the
        oracle semantics — one prompt prefilled at a time)."""
        for sid, prompt in prompts.items():
            self.add_request(sid, prompt)

    def add_request(self, sid: int, prompt: list[int]) -> None:
        """Blocking admission: chunked prefill driven to completion.  The
        canonical-prefix attention rule is chunk-layout-independent, so
        one full-width advance equals any budgeted chunking."""
        self.begin_request(sid, prompt)
        while self.seqs[sid].prefilling:
            self.prefill_advance(sid, len(prompt))

    def release(self, sid: int) -> None:
        """Retire a request: free its private pool pages and unpin its
        shared prefix chain (oracle parity with the batched engine's slot
        recycling — the reference has no slots)."""
        seq = self.seqs.pop(sid)
        assert not (seq.prefilling and not seq.preempted), \
            f"sid {sid} is mid-prefill; cannot release"
        self._drop_seq_pages(seq, count_evicted=False)
        if self.prefix_cache is not None:
            # reclaim quarantined entries the moment their last pin drops
            self.free.extend(self.prefix_cache.purge_corrupt())

    def abort(self, sid: int) -> None:
        """Abandon a request mid-flight (deadline miss, integrity
        restart): drop its pages and mark it preempted so ``release``
        accepts it even mid-prefill (mirror of the batched engine)."""
        seq = self.seqs[sid]
        if seq.preempted:
            return
        self._drop_seq_pages(seq, count_evicted=False)
        seq.tail_len = 0
        seq.preempted = True
        seq.pf_k = seq.pf_v = seq.pf_kc = seq.pf_vc = None

    # -- integrity / invariants ------------------------------------------------

    def verify_seq(self, sid: int) -> bool:
        """Recompute checksums for every pool page the sequence maps;
        quarantines corrupt shared entries.  See serving/faults.py."""
        return F.verify_seq(self, sid)

    def debug_validate(self) -> None:
        """Assert page/refcount accounting is exact (test teardowns and
        chaos drains).  See :func:`repro.serving.faults.debug_validate`."""
        F.debug_validate(self)

    # -- chunked-prefill oracle (mixed-schedule semantics) ---------------------

    def begin_request(self, sid: int, prompt: list[int]) -> int:
        """Admit a prompt for *chunked* prefill without running any of it.

        The mixed-schedule oracle twin of ``PagedKVEngine.begin_cohort``:
        consults the prefix cache, pins and maps the cached chain, and
        arranges for prefill to start at the hit boundary.  Returns the
        number of cached prompt tokens (0 when cold / no cache).  The
        continuous-batching scheduler advances the prompt
        ``prefill_advance(n)`` tokens per iteration, interleaved with
        ``decode_one`` calls, and the result must be token-for-token
        identical to full-prompt ``add_request`` prefill.
        """
        cfg = self.cfg
        lyr, k, dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        assert sid not in self.seqs, sid
        assert prompt, f"empty prompt for sid {sid}"
        page = self.page
        stored = len(prompt) - 1
        start, chain = 0, []
        if self.prefix_cache is not None:
            start, chain = self.prefix_cache.lookup(prompt)
            if self.integrity:
                # warm-hit trust boundary: never map a corrupt shared
                # page — truncate the chain and recompute from there
                vstart, chain = F.verified_prefix(self, start, chain)
                if vstart != start:
                    self._m["integrity_failures"].inc()
                    start = vstart
            self.prefix_cache.pin(chain)
        ent = [self.prefix_cache.entries[e] for e in chain]
        seq = Sequence(
            sid=sid, tokens=list(prompt),
            pages=[[e.pages[li] for e in ent] for li in range(lyr)],
            tail_k=np.zeros((lyr, page, k, dh), np.float32),
            tail_v=np.zeros((lyr, page, k, dh), np.float32),
            chain=list(chain), pf_start=start, pf_pos=start,
            pf_published=start // page)
        self.seqs[sid] = seq
        if start >= stored:
            return start           # full prefix hit: straight to decode
        seq.prefilling = True
        # scratch sizing mirrors the batched engine's formula (any
        # page-aligned size is bit-equivalent; matching it maximizes jit
        # cache reuse when both engines run side by side)
        chunk = self.prefill_chunk
        n_chunks = -(-stored // chunk) + 1
        cap = 1
        while cap < n_chunks:
            cap *= 2
        tpad = cap * chunk
        pf_k = np.zeros((lyr, 1, tpad, k, dh), np.float32)
        pf_v = np.zeros((lyr, 1, tpad, k, dh), np.float32)
        # decompress the cached prefix into the scratch warm region: the
        # canonical values decode-side attention reads for those pages
        # (same codec helper as decode; elementwise, so bit-equal to the
        # engine's jitted fill)
        for b in range(start // page):
            sl = slice(b * page, (b + 1) * page)
            for li in range(lyr):
                pid = seq.pages[li][b]
                kk, vv = self.codec.decompress_pages(jax.tree.map(
                    lambda a: jnp.asarray(a[pid][None]), self.pools))
                pf_k[li, 0, sl] = np.swapaxes(np.asarray(kk[0]), 0, 1)
                pf_v[li, 0, sl] = np.swapaxes(np.asarray(vv[0]), 0, 1)
        seq.pf_k = jnp.asarray(pf_k)
        seq.pf_v = jnp.asarray(pf_v)
        # the warm region is canonical by construction; the rest of the
        # canonical view fills in window-by-window as chunks complete.
        # Lossless codecs never read it (identity prefill attention) and
        # carry a zero-length view, mirroring the batched engine.
        if self.codec.lossless:
            seq.pf_kc = jnp.zeros((lyr, 1, 0, k, dh), jnp.float32)
            seq.pf_vc = jnp.zeros_like(seq.pf_kc)
        else:
            seq.pf_kc = jnp.asarray(pf_k)
            seq.pf_vc = jnp.asarray(pf_v)
        return start

    def prefill_advance(self, sid: int, n: int) -> bool:
        """Advance a chunked prefill by up to ``n`` prompt tokens.

        The chunk's compute runs through the shared jitted dispatch
        (``engine._prefill_chunk``, one scratch row — see the module
        docstring for why numerics must be shared); pages completed by
        the chunk publish through this engine's own CAMP-accounted
        numpy-pool path and register in the prefix cache, and the final
        partial page lands in the decode tail buffer.  Returns True when
        prefill completed.
        """
        cfg, seq, page = self.cfg, self.seqs[sid], self.page
        assert seq.prefilling, f"sid {sid} is not prefilling"
        stored = len(seq.tokens) - 1
        chunk = self.prefill_chunk
        n = min(n, stored - seq.pf_pos)
        while n > 0:
            step = min(n, chunk)
            p = seq.pf_pos
            tpad = seq.pf_k.shape[2]
            off = min(p, tpad - chunk)
            pt = np.zeros((1, chunk), np.int32)
            w = min(chunk, len(seq.tokens) - off)
            pt[0, :w] = seq.tokens[off:off + w]
            pt[0, step:] = 0                  # budget-split masking
            seq.pf_k, seq.pf_v, seq.pf_kc, seq.pf_vc = _E._prefill_chunk(
                self.params, jnp.asarray(pt), seq.pf_k, seq.pf_v,
                seq.pf_kc, seq.pf_vc, jnp.asarray([off], jnp.int32),
                cfg=cfg, page=page, codec=self.codec)
            seq.pf_pos = p + step
            n -= step
            # publish every page the chunk completed (block-outer order —
            # page *sets* match the batched path, and CAMP victim choice
            # is order-independent in the supported scenarios)
            for blk in range(seq.pf_published, seq.pf_pos // page):
                sl = slice(blk * page, (blk + 1) * page)
                self._publish_block(seq,
                                    np.asarray(seq.pf_k[:, 0, sl]),
                                    np.asarray(seq.pf_v[:, 0, sl]),
                                    blk=blk)
                seq.pf_published = blk + 1
            if seq.preempted:
                break
        if seq.pf_pos < stored and not seq.preempted:
            return False
        seq.prefilling = False
        seq.tail_len = 0 if seq.preempted else stored % page
        if seq.tail_len:
            base = (stored // page) * page
            tk = np.asarray(seq.pf_k[:, 0, base:stored])
            tv = np.asarray(seq.pf_v[:, 0, base:stored])
            for li in range(cfg.n_layers):
                seq.tail_k[li, :seq.tail_len] = tk[li]
                seq.tail_v[li, :seq.tail_len] = tv[li]
        seq.pf_k = seq.pf_v = seq.pf_kc = seq.pf_vc = None
        return True

    def _block_params(self, li: int):
        return jax.tree.map(lambda x: x[li], self.params["blocks"])

    # -- decode ------------------------------------------------------------------

    def decode_one(self, sid: int) -> int:
        """Greedy-decode one token for sequence sid."""
        cfg, seq = self.cfg, self.seqs[sid]
        assert not seq.prefilling, f"sid {sid} is mid-prefill; cannot decode"
        t = len(seq.tokens)
        tok = jnp.asarray([seq.tokens[-1]], jnp.int32)
        x = L.embed(self.params["embed"], tok[:, None])
        for li in range(cfg.n_layers):
            bp = self._block_params(li)
            h = L.rmsnorm(bp["ln1"], x, cfg.norm_eps)
            q = L.linear(bp["attn"]["wq"], h)
            k_new = L.linear(bp["attn"]["wk"], h)
            v_new = L.linear(bp["attn"]["wv"], h)
            dh = q.shape[-1]
            pos_t = jnp.asarray([t - 1], jnp.int32)
            cos, sin = L.rope_angles(pos_t, dh, cfg.rope_theta)
            q = L.apply_rope(q, cos[None, :, None, :], sin[None, :, None, :])
            k_new = L.apply_rope(k_new, cos[None, :, None, :],
                                 sin[None, :, None, :])
            seq.tail_k[li, seq.tail_len] = np.asarray(k_new[0, 0], np.float32)
            seq.tail_v[li, seq.tail_len] = np.asarray(v_new[0, 0], np.float32)

            ctx = self._attend(seq, li, q)
            x = x + A._proj_out(bp["attn"], ctx)
            h2 = L.rmsnorm(bp["ln2"], x, cfg.norm_eps)
            x = x + L.mlp(bp["ffn"], h2)
        seq.tail_len += 1
        if seq.tail_len == self.page:
            self._publish_block(seq, seq.tail_k, seq.tail_v)
            seq.tail_len = 0

        x = L.rmsnorm(self.params["final_norm"], x, cfg.norm_eps)
        logits = L.lm_logits(self.params["lm_head"], x)[0, 0]
        nxt = int(jnp.argmax(logits))
        if self.faults is not None:
            nxt = self.faults.garble_one(nxt)
        seq.tokens.append(nxt)
        return nxt

    def _attend(self, seq: Sequence, li: int, q: jax.Array) -> jax.Array:
        cfg = self.cfg
        kh, dh = cfg.n_kv_heads, cfg.head_dim
        pids = seq.pages[li]
        parts_k, parts_v = [], []
        if pids:
            k_pages, v_pages = self.codec.decompress_pages(jax.tree.map(
                lambda a: jnp.asarray(a[pids]), self.pools))
            parts_k.append(jnp.swapaxes(k_pages, 1, 2).reshape(-1, kh, dh))
            parts_v.append(jnp.swapaxes(v_pages, 1, 2).reshape(-1, kh, dh))
        tl = seq.tail_len + 1
        parts_k.append(jnp.asarray(seq.tail_k[li, :tl]))
        parts_v.append(jnp.asarray(seq.tail_v[li, :tl]))
        k = jnp.concatenate(parts_k, axis=0)           # [T, K, Dh]
        v = jnp.concatenate(parts_v, axis=0)
        hq = q.shape[2]
        qg = q[0, 0].reshape(kh, hq // kh, dh)
        sc = jnp.einsum("kgd,tkd->kgt", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(jnp.float32(dh))
        w = jax.nn.softmax(sc, axis=-1)
        ctx = jnp.einsum("kgt,tkd->kgd", w, v.astype(jnp.float32))
        return ctx.reshape(1, 1, hq, dh).astype(q.dtype)

    # -- metrics ------------------------------------------------------------------

    def compression_ratio(self) -> float:
        if not self._m["bytes_compressed"].value:
            return 1.0
        return self._m["bytes_raw"].value / self._m["bytes_compressed"].value

    def pool_used_pages(self) -> int:
        return (self.n_pool_pages - 1) - len(self.free)
