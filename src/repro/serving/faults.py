"""Resilience layer: fault taxonomy, injection, page integrity, invariants.

The thesis' core discipline is that compression is only practical when
the *exception paths* are first-class — LCP's design is dominated by
cheap overflow/exception handling, and CRAM ships its win only next to
an explicit fallback-to-uncompressed path.  This module is the serving
stack's equivalent: everything the engines and scheduler need to keep
the compressed-KV serving loop correct when pages corrupt, pools
exhaust, logits go to garbage, or traffic bursts past capacity.

Four pieces live here:

  * :class:`FinishReason` — the unified terminal taxonomy shared by the
    engines, :class:`~repro.serving.scheduler.ContinuousScheduler`, and
    ``launch/serve.py``.  A ``str`` subclass, so existing
    ``finish_reason == "eos"`` comparisons keep working.
  * **Page integrity** — a cheap per-page checksum
    (:func:`page_checksums`: a weighted byte sum in wrapping uint32,
    computed *inside* the engines' existing publish dispatch so it rides
    the one host sync per publish) plus the verification helpers both
    engines call at the trust boundaries: warm prefix-cache hits at
    admission (:func:`verified_prefix`), request retirement
    (:func:`verify_seq`), and preemption victims before their pages are
    dropped.  A mismatch never serves tokens: the scheduler restarts the
    request from its *original* prompt (capped retries + backoff), so
    detection latency cannot leak corrupted-influenced tokens into a
    final answer.
  * :class:`FaultInjector` — deterministic, seedable fault injection
    with hook points in engine publish (compressed-page bit corruption
    — covering both the publish scatter and the codec roundtrip, since
    the flip lands in the compressed bytes the next gather decompresses),
    decode argmax (garbage tokens modeling NaN logits), the scheduler
    iteration (pool-allocation failure via bounded free-list holds), and
    the arrival process (bursts).  Same seed + same spec => the same
    fault schedule, byte for byte (``injector.log`` records it).
  * :func:`debug_validate` — the engine invariant checker: every pool
    page is owned by exactly one of {free list, injector hold, live
    sequence, prefix-cache entry}; prefix-cache refcounts equal live
    pins; batch slots partition exactly (batched engine).  Tests call it
    at drain so leaks fail loudly instead of incidentally.

No engine imports here (the engines import *us*): every helper takes the
engine duck-typed, which is also what lets one implementation serve both
``PagedKVEngine`` (device jnp pools) and ``ReferencePagedKVEngine``
(host numpy pools).
"""

from __future__ import annotations

import enum
from collections import Counter
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np


class FinishReason(str, enum.Enum):
    """Terminal request outcomes (str-valued: ``== "eos"`` still works)."""
    EOS = "eos"                  # emitted the request's eos_id
    LENGTH = "length"            # reached max_new_tokens
    PREEMPTED = "preempted"      # CAMP-preempted past the requeue limit
    REJECTED = "rejected"        # bounded queue / overload admission reject
    DEADLINE = "deadline"        # TTFT or total deadline exceeded
    CORRUPTED = "corrupted-retries-exhausted"  # integrity retries exhausted

    def __str__(self) -> str:          # repr/str parity with plain strings
        return self.value


class PoolExhaustedError(RuntimeError):
    """Page reservation found nothing evictable (pool truly exhausted)."""


class SchedulerStalledError(RuntimeError):
    """The scheduler made no progress for ``stall_limit`` iterations."""


# a token id no vocabulary contains: what a NaN-logit argmax degenerates
# to in this fault model; the scheduler's range check catches it the same
# iteration it is emitted
GARBAGE_TOKEN = -(1 << 20)


# ---------------------------------------------------------------------------
# per-page checksums
# ---------------------------------------------------------------------------

_MIX = jnp.uint32(2654435761)            # Knuth multiplicative hash constant


def page_checksums(pg) -> jax.Array:
    """Position-weighted byte sum per page, wrapping uint32.

    ``pg`` is a codec page pytree whose leaves lead with the page axis
    ``[n, ...]`` (any dtypes).  Returns uint32 ``[n]``.  Pure jnp — the
    engines call it *inside* their publish dispatch (zero extra host
    syncs) and from the jitted gather used at verification time, so
    publish-side and verify-side values are computed by the same code on
    the same bits.  The position weighting (vs a plain sum) catches
    byte swaps and single-bit flips anywhere in the page.
    """
    leaves = [lf for lf in jax.tree.leaves(pg) if lf.size]
    n = leaves[0].shape[0]
    acc = jnp.zeros(n, jnp.uint32)
    for lf in leaves:
        b = jax.lax.bitcast_convert_type(lf, jnp.uint8).reshape(n, -1)
        w = jnp.arange(b.shape[1], dtype=jnp.uint32) * _MIX + jnp.uint32(1)
        acc = acc + jnp.sum(b.astype(jnp.uint32) * w[None, :], axis=1,
                            dtype=jnp.uint32)
        acc = acc * _MIX + jnp.uint32(1)   # leaf order matters too
    return acc


_checksum_jit = jax.jit(page_checksums)


@jax.jit
def _gather_checksums(pools, pids):
    """Checksum pool pages ``pids[j]`` in one dispatch."""
    return page_checksums(jax.tree.map(lambda a: a[pids], pools))


def pair_checksums(engine, pairs: list[tuple[int, int]]) -> np.ndarray:
    """Recompute checksums for ``(layer, pid)`` pool pages (uint32 [n]).

    Dispatch-shape discipline: device pools gather through a jit whose
    index length is padded to a power of two (retraces stay logarithmic
    in the largest verification batch); numpy pools gather host-side and
    checksum at the exact length.
    """
    pa = np.asarray([p[1] for p in pairs], np.int32)
    leaves = jax.tree.leaves(engine.pools)
    if isinstance(leaves[0], np.ndarray):
        pg = jax.tree.map(lambda a: jnp.asarray(a[pa]), engine.pools)
        return np.asarray(_checksum_jit(pg))
    n = len(pairs)
    cap = 1
    while cap < n:
        cap *= 2
    pp = np.zeros(cap, np.int32)          # 0: the padding page
    pp[:n] = pa
    out = _gather_checksums(engine.pools, jnp.asarray(pp))
    with engine.telemetry.tracer.span("engine.sync"):
        out = np.asarray(out)
    return out[:n]


def verify_pages(engine, pairs: list[tuple[int, int]]) -> np.ndarray:
    """bool [n]: does each ``(layer, pid)`` page still match its
    publish-time checksum?"""
    if not pairs:
        return np.ones(0, bool)
    got = pair_checksums(engine, pairs)
    want = np.asarray([engine.page_checksum[p] for _, p in pairs],
                      np.uint32)
    return got == want


def verify_seq(engine, sid: int) -> bool:
    """Verify every pool page a sequence maps; quarantine corrupt shared
    prefix entries so later lookups skip them.  Sets ``seq.corrupted``
    (and returns False) on any mismatch — the scheduler turns that into
    a restart-from-original-prompt."""
    seq = engine.seqs[sid]
    lyr = engine.cfg.n_layers
    pairs = [(li, pid) for li in range(lyr) for pid in seq.pages[li]]
    if not pairs:
        return True
    ok = verify_pages(engine, pairs)
    if ok.all():
        return True
    ns = len(seq.chain)
    if ns and engine.prefix_cache is not None:
        nblk = len(seq.pages[0])
        for j, good in enumerate(ok):
            blk = j % nblk                 # pairs are layer-major
            if not good and blk < ns:
                engine.prefix_cache.quarantine(seq.chain[blk])
    seq.corrupted = True
    return False


def verified_prefix(engine, start: int, chain: list[int]
                    ) -> tuple[int, list[int]]:
    """Admission-time warm-hit verification: truncate a looked-up prefix
    chain at its first corrupt entry (quarantining it) so a warm request
    never maps bad pages — it recomputes from the truncation point like
    a shorter hit.  Returns the (possibly shortened) ``(start, chain)``.
    """
    cache = engine.prefix_cache
    if not chain:
        return start, chain
    lyr, page = engine.cfg.n_layers, engine.page
    pairs = [(li, cache.entries[eid].pages[li])
             for eid in chain for li in range(lyr)]
    ok = verify_pages(engine, pairs)
    for b, eid in enumerate(chain):
        if not ok[b * lyr:(b + 1) * lyr].all():
            cache.quarantine(eid)
            engine.free.extend(cache.purge_corrupt())
            return b * page, chain[:b]
    return start, chain


# ---------------------------------------------------------------------------
# fault injection
# ---------------------------------------------------------------------------

@dataclass
class FaultSpec:
    """Deterministic fault schedule (all counters start at 1).

    ``corrupt_page_every=N``: every Nth *published page* (either engine,
    counted per page across layers) gets one bit flipped in its
    compressed pool bytes, after its checksum is recorded — the model of
    bit rot / torn writes in compressed storage, and of a corrupting
    codec roundtrip (the flip is what the next gather decompresses).
    ``garble_decode_every=N``: every Nth decode dispatch replaces one
    active sequence's argmax with :data:`GARBAGE_TOKEN` (NaN-logit
    model), *inside* the engine — the garbage lands in the sequence's
    token state exactly as a real NaN argmax would.
    ``holds``: ``(start_iter, n_pages, duration_iters)`` windows during
    which ``n_pages`` free-list pages are unallocatable — the
    pool-allocation-failure model, driving eviction/preemption/overload
    machinery exactly like real pressure.
    ``bursts``: ``{iteration: extra_requests}`` consumed by the workload
    driver via :meth:`FaultInjector.burst`.
    """
    corrupt_page_every: int = 0
    corrupt_max: int | None = None
    garble_decode_every: int = 0
    garble_max: int | None = None
    holds: tuple[tuple[int, int, int], ...] = ()
    bursts: dict[int, int] = field(default_factory=dict)


class FaultInjector:
    """Seeded deterministic fault injector over a serving engine.

    One injector serves one engine; hand the same instance to the
    engine (publish/decode hooks) and scheduler (iteration hook).  All
    randomness comes from one ``np.random.default_rng(seed)`` consumed
    only when a fault fires, so the event ``log`` is a pure function of
    ``(spec, seed)`` and the workload.
    """

    def __init__(self, spec: FaultSpec | None = None, *, seed: int = 0):
        self.spec = spec or FaultSpec()
        self.seed = seed
        self.rng = np.random.default_rng(seed)
        self.log: list[tuple] = []
        self._pub_ctr = 0
        self._dec_ctr = 0
        self._holds: list[tuple[int, list[int]]] = []   # (release_iter, pids)
        self._holds_started: set[int] = set()
        self.stats = {"corruptions": 0, "garbled": 0, "pages_held": 0}
        # set by the owning engine (serving/telemetry.py); injected-fault
        # counters are pushed into its registry by :meth:`sample_metrics`
        self.telemetry = None

    def sample_metrics(self) -> None:
        """Mirror injector counters into the attached telemetry registry
        (export-time, off the injection hot path)."""
        if self.telemetry is None:
            return
        reg = self.telemetry.registry
        for k, v in self.stats.items():
            reg.gauge(f"faults_{k}_injected").set(v)

    # -- compressed-page corruption (publish / codec-roundtrip hook) -------

    def page_published(self, engine, layer: int, pid: int) -> None:
        """Engine hook: called once per freshly published (layer, page)."""
        sp = self.spec
        if not sp.corrupt_page_every:
            return
        if sp.corrupt_max is not None \
                and self.stats["corruptions"] >= sp.corrupt_max:
            return
        self._pub_ctr += 1
        if self._pub_ctr % sp.corrupt_page_every == 0:
            self.corrupt_page(engine, layer, pid)

    def corrupt_page(self, engine, layer: int, pid: int,
                     bit: int | None = None) -> None:
        """Flip one bit of a pool page's compressed bytes (first nonempty
        codec leaf).  Works on device jnp pools (functional ``.at[]``
        write) and host numpy pools (in-place) alike."""
        leaves, treedef = jax.tree_util.tree_flatten(engine.pools)
        li = next(i for i, lf in enumerate(leaves) if lf[pid].size)
        pg = np.asarray(leaves[li][pid])
        raw = bytearray(pg.tobytes())
        if bit is None:
            bit = int(self.rng.integers(0, len(raw) * 8))
        raw[(bit // 8) % len(raw)] ^= 1 << (bit % 8)
        new = np.frombuffer(bytes(raw), dtype=pg.dtype).reshape(pg.shape)
        if isinstance(leaves[li], np.ndarray):
            leaves[li][pid] = new
        else:
            leaves[li] = leaves[li].at[pid].set(jnp.asarray(new))
            engine.pools = jax.tree_util.tree_unflatten(treedef, leaves)
        self.stats["corruptions"] += 1
        self.log.append(("corrupt", layer, pid, bit))

    # -- garbage decode logits (argmax hook) -------------------------------

    def _garble_fires(self) -> bool:
        sp = self.spec
        if not sp.garble_decode_every:
            return False
        if sp.garble_max is not None \
                and self.stats["garbled"] >= sp.garble_max:
            return False
        self._dec_ctr += 1
        return self._dec_ctr % sp.garble_decode_every == 0

    def garble_tokens(self, nxt: np.ndarray, slots: list[int]) -> np.ndarray:
        """Batched-engine hook: maybe replace one active slot's token."""
        if not slots or not self._garble_fires():
            return nxt
        slot = slots[int(self.rng.integers(0, len(slots)))]
        nxt = nxt.copy()
        nxt[slot] = GARBAGE_TOKEN
        self.stats["garbled"] += 1
        self.log.append(("garble", slot))
        return nxt

    def garble_one(self, tok: int) -> int:
        """Reference-engine hook: maybe replace one decoded token."""
        if not self._garble_fires():
            return tok
        self.stats["garbled"] += 1
        self.log.append(("garble", -1))
        return GARBAGE_TOKEN

    # -- pool-allocation failure (scheduler iteration hook) ----------------

    def on_iteration(self, engine, iteration: int) -> None:
        """Start/expire free-list holds scheduled for this iteration."""
        for start, n, dur in self.spec.holds:
            if iteration >= start and start not in self._holds_started:
                self._holds_started.add(start)
                take = min(n, len(engine.free))
                pids = [engine.free.pop() for _ in range(take)]
                self._holds.append((start + dur, pids))
                self.stats["pages_held"] += take
                self.log.append(("hold", start, take))
        kept = []
        for release, pids in self._holds:
            if iteration >= release:
                engine.free.extend(pids)
                self.log.append(("release", release, len(pids)))
            else:
                kept.append((release, pids))
        self._holds = kept

    def release_holds(self, engine) -> None:
        """Return every held page (used at drain / teardown)."""
        for _, pids in self._holds:
            engine.free.extend(pids)
        self._holds = []

    @property
    def held_pages(self) -> list[int]:
        return [pid for _, pids in self._holds for pid in pids]

    # -- arrival bursts (workload-driver hook) -----------------------------

    def burst(self, iteration: int) -> int:
        """Extra requests the driver should submit at this iteration."""
        return self.spec.bursts.get(iteration, 0)


# ---------------------------------------------------------------------------
# engine invariant checker
# ---------------------------------------------------------------------------

def debug_validate(engine) -> None:
    """Assert the engine's page/refcount/slot accounting is exact.

    Every pool page (ids 1..P-1; 0 is the padding page) is owned by
    exactly one of: the free list, an injector hold, a live sequence's
    private pages, or a prefix-cache entry.  Shared chain pages map the
    cache entry's pages verbatim; cache refcounts equal live pins;
    children counters match the trie; batch slots partition exactly
    (batched engine).  Raises AssertionError on any violation.
    """
    cap = engine.n_pool_pages - 1
    free = engine.free
    free_set = set(free)
    assert len(free_set) == len(free), "duplicate pages on the free list"
    assert 0 not in free_set, "padding page 0 on the free list"

    held = set(engine.faults.held_pages) if getattr(engine, "faults", None) \
        else set()
    cache = engine.prefix_cache
    cache_pages = {pid for e in cache.entries.values() for pid in e.pages} \
        if cache is not None else set()

    lyr = engine.cfg.n_layers
    private: list[int] = []
    for s in engine.seqs.values():
        ns = len(s.chain)
        for li in range(lyr):
            assert len(s.pages[li]) == len(s.pages[0]), \
                f"sid {s.sid}: ragged page lists"
            private.extend(s.pages[li][ns:])
            for b, eid in enumerate(s.chain):
                assert s.pages[li][b] == cache.entries[eid].pages[li], \
                    f"sid {s.sid} layer {li} block {b}: chain mapping drift"
    private_set = set(private)
    assert len(private_set) == len(private), \
        "a private page is mapped twice"

    for a, b, what in [(free_set, private_set, "free∩private"),
                       (free_set, cache_pages, "free∩cache"),
                       (free_set, held, "free∩held"),
                       (private_set, cache_pages, "private∩cache"),
                       (held, private_set | cache_pages, "held∩mapped")]:
        assert not (a & b), f"page owned twice ({what}): {sorted(a & b)}"
    total = len(free_set) + len(held) + len(private_set) + len(cache_pages)
    assert total == cap, (f"page leak: free {len(free_set)} + held "
                          f"{len(held)} + private {len(private_set)} + "
                          f"cache {len(cache_pages)} != pool {cap}")

    if cache is not None:
        pins = Counter(eid for s in engine.seqs.values() for eid in s.chain)
        for eid, e in cache.entries.items():
            assert e.refcount == pins.get(eid, 0), \
                f"entry {eid}: refcount {e.refcount} != {pins.get(eid, 0)} pins"
        kids = Counter(e.parent for e in cache.entries.values() if e.parent)
        for eid, e in cache.entries.items():
            assert e.children == kids.get(eid, 0), \
                f"entry {eid}: children {e.children} != {kids.get(eid, 0)}"
            assert e.parent == 0 or e.parent in cache.entries, \
                f"entry {eid}: dangling parent {e.parent}"

    if hasattr(engine, "_free_slots"):   # batched engine only
        slots = list(engine._free_slots) \
            + [s.slot for s in engine.seqs.values()]
        assert sorted(slots) == list(range(engine.max_batch)), \
            f"slot accounting drift: {sorted(slots)}"
