"""Host/disk memory tier (serving/tier.py): correctness + policy suite.

Covers the demotion/promotion data path end to end: demote -> promote
round trips that are bit-identical in the device pool across every
registered codec, the host-side checksum replica pinned against the
device implementation, corrupt host-arena slots quarantined instead of
served, persist/restore across an engine "restart" (warm TTFT
equivalence), engine snapshot round trips that carry the tier, the
LCP-linear arithmetic addressing contract (no per-page offset table),
the GlobalCache eviction/deletion split (demotion hook sees victims
without changing eviction order), and multi-turn decode-page caching
past the prompt-page boundary.
"""

import jax
import numpy as np
import pytest

from repro.configs.registry import get_arch
from repro.models.api import get_model
from repro.serving import faults as F
from repro.serving import tier as T
from repro.serving.engine import PagedKVEngine
from repro.serving.prefix_cache import PrefixCache
from repro.serving.tier import TieredPageStore

PAGE = 8
CODECS = ("bdi", "zero", "raw", "gbdi", "fpc", "adaptive")


@pytest.fixture(scope="module")
def small_model():
    cfg = get_arch("yi-6b").reduced(n_layers=2, d_model=64)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


def _tiered_engine(cfg, params, *, codec=None, host_mb=4, disk_dir=None,
                   disk_mb=None, cache_decode_pages=False, pool=96):
    cache = PrefixCache.for_model(cfg, PAGE)
    eng = PagedKVEngine(cfg, params, page_size=PAGE, n_pool_pages=pool,
                        max_batch=4, prefix_cache=cache, codec=codec,
                        cache_decode_pages=cache_decode_pages)
    tier = TieredPageStore.for_model(cfg, PAGE, eng.codec, host_mb=host_mb,
                                     disk_dir=disk_dir, disk_mb=disk_mb)
    eng.attach_tier(tier)
    return eng, cache, tier


def _prompt(n, stride=7):
    return [1 + (j * stride) % 50 for j in range(n)]


def _entry_page_state(eng, eid):
    """One cache entry's device-resident bytes + publish metadata."""
    e = eng.prefix_cache.entries[eid]
    leaves = [np.stack([np.asarray(lf[e.pages[li]])
                        for li in range(eng.cfg.n_layers)])
              for lf in jax.tree.leaves(eng.pools)]
    meta = [(int(eng.page_bytes[p]), int(eng.page_codec_id[p]),
             int(eng.page_checksum[p])) for p in e.pages]
    return leaves, meta


# ---------------------------------------------------------------------------
# demote -> promote round trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec", CODECS)
def test_demote_promote_roundtrip_bit_identical(small_model, codec):
    """Full cycle under every codec: run a prompt, recycle the entire
    device pool (forcing SIP eviction to demote instead of drop),
    re-admit the same prompt, and require (a) identical greedy tokens,
    (b) bit-identical pool pages and publish metadata for every
    promoted block, and (c) nonzero demotion/promotion counters."""
    cfg, params = small_model
    eng, cache, tier = _tiered_engine(cfg, params, codec=codec)
    prompt = _prompt(33)                      # 32 stored tokens: 4 pages

    eng.add_requests({0: prompt})
    cold = [eng.decode_one(0) for _ in range(6)]
    chain0 = list(eng.seqs[0].chain)
    before = [_entry_page_state(eng, eid) for eid in chain0]
    eng.release(0)

    freed = eng.recycle_device_pool()
    assert freed >= 4 * cfg.n_layers
    assert not cache.entries
    assert tier.stats["demotions"] >= 4
    assert tier.stats["promotions"] == 0
    eng.debug_validate()

    cached = eng.add_requests({1: prompt})[1]
    assert cached == 32                       # every stored page promoted
    assert tier.stats["promotions"] == 4
    warm = [eng.decode_one(1) for _ in range(6)]
    assert warm == cold

    after = [_entry_page_state(eng, eid) for eid in eng.seqs[1].chain]
    assert len(after) == len(before)
    for (bl, bm), (al, am) in zip(before, after):
        assert bm == am                       # nbytes / codec tag / checksum
        for x, y in zip(bl, al):
            assert np.array_equal(x, y)       # compressed bits themselves
    eng.release(1)
    eng.debug_validate()

    # acceptance: the counters reach the exported registry
    eng.sample_gauges()
    snap = eng.telemetry.registry.snapshot()
    assert snap["tier_demotions_total"]["series"][0]["value"] >= 4
    assert snap["tier_promotions_total"]["series"][0]["value"] == 4
    assert snap["tier_promotion_seconds"]["series"][0]["count"] >= 1


def test_np_checksums_match_device_implementation():
    """np_page_checksums must be bit-equal to faults.page_checksums on
    the same leaves — promotion-time verification runs entirely on the
    host against checksums the device computed at publish time."""
    rng = np.random.default_rng(7)
    leaves = [
        rng.standard_normal((5, 3, 4)).astype(np.float32),
        rng.integers(0, 256, (5, 17), dtype=np.uint8),
        rng.integers(-2**31, 2**31 - 1, (5, 2, 3), dtype=np.int32),
        np.zeros((5, 0, 4), np.float32),       # empty leaf is skipped
    ]
    import jax.numpy as jnp
    dev = np.asarray(F.page_checksums([jnp.asarray(x) for x in leaves]))
    host = T.np_page_checksums(leaves)
    assert host.dtype == np.uint32
    assert np.array_equal(dev, host)


# ---------------------------------------------------------------------------
# integrity
# ---------------------------------------------------------------------------

def test_corrupt_host_slot_quarantined_not_served(small_model):
    """A flipped byte in the host arena fails promotion-time checksum
    verification: the record is quarantined (truncating the warm hit),
    the request recomputes and still produces correct tokens, and a
    later demotion heals the slot with fresh bytes."""
    cfg, params = small_model
    eng, cache, tier = _tiered_engine(cfg, params)
    prompt = _prompt(33, stride=11)

    eng.add_requests({0: prompt})
    cold = [eng.decode_one(0) for _ in range(6)]
    eng.release(0)
    eng.recycle_device_pool()

    recs = tier.lookup(prompt)
    assert len(recs) == 4
    victim = recs[-1]
    tier.host.buf[victim.slot, 5] ^= 0xFF      # silent host-RAM bit rot

    cached = eng.add_requests({1: prompt})[1]
    assert cached == 24                        # hit truncated at block 3
    assert victim.corrupt
    assert tier.stats["corrupt"] == 1
    warm = [eng.decode_one(1) for _ in range(6)]
    assert warm == cold                        # recomputed, never served bad
    eng.release(1)

    # quarantined records are skipped by lookup until healed
    assert len(tier.lookup(prompt)) == 3
    eng.recycle_device_pool()                  # re-demotes block 3 -> heal
    assert not tier._records[victim.digest].corrupt
    assert len(tier.lookup(prompt)) == 4
    eng.debug_validate()


# ---------------------------------------------------------------------------
# persist / restore
# ---------------------------------------------------------------------------

def test_persist_restore_across_restart_warm(small_model, tmp_path):
    """The tier persisted through checkpoint/store.py restores into a
    fresh engine ("process restart") and serves the same warm hits with
    identical tokens — nothing re-prefill beyond the unstored tail."""
    cfg, params = small_model
    eng, cache, tier = _tiered_engine(cfg, params)
    prompt = _prompt(33, stride=5)
    eng.add_requests({0: prompt})
    cold = [eng.decode_one(0) for _ in range(6)]
    eng.release(0)
    eng.recycle_device_pool()
    n_recs = tier.record_count()
    assert n_recs >= 4
    tier.persist(str(tmp_path), step=3)

    eng2, cache2, _ = _tiered_engine(cfg, params)   # fresh "process"
    tier2 = TieredPageStore.restore(str(tmp_path), cfg, eng2.codec,
                                    host_mb=4)
    assert tier2.record_count() == n_recs
    eng2.tier = None                                # replace the fresh tier
    eng2.prefix_cache.demote_cb = None
    eng2.attach_tier(tier2)

    cached = eng2.add_requests({0: prompt})[0]
    assert cached == 32
    assert tier2.stats["promotions"] == 4
    assert [eng2.decode_one(0) for _ in range(6)] == cold
    eng2.debug_validate()


def test_restore_refuses_wrong_component_kind(small_model, tmp_path):
    """The kind stamp keeps a tier checkpoint from being restored as a
    different component (and vice versa)."""
    from repro.checkpoint import store
    store.persist(str(tmp_path), 0, {"x": np.zeros(4, np.uint8)},
                  {"a": 1}, kind="engine-snapshot")
    cfg, params = small_model
    codec = PagedKVEngine(cfg, params, page_size=PAGE,
                          n_pool_pages=32, max_batch=1).codec
    with pytest.raises(AssertionError, match="kind"):
        TieredPageStore.restore(str(tmp_path), cfg, codec)


def test_engine_snapshot_carries_tier(small_model, tmp_path):
    """serving/snapshot.py round-trips the attached tier: a restored
    engine promotes the pre-kill conversation without re-demotion."""
    from repro.serving.snapshot import restore_snapshot, save_snapshot
    cfg, params = small_model
    eng, cache, tier = _tiered_engine(cfg, params)
    prompt = _prompt(33, stride=13)
    eng.add_requests({0: prompt})
    cold = [eng.decode_one(0) for _ in range(6)]
    eng.release(0)
    eng.recycle_device_pool()
    save_snapshot(str(tmp_path), eng, step=1)

    eng2, _ = restore_snapshot(str(tmp_path), cfg, params, step=1)
    assert eng2.tier is not None
    assert eng2.tier.record_count() == tier.record_count()
    cached = eng2.add_requests({5: prompt})[5]
    assert cached == 32
    assert eng2.tier.stats["promotions"] == tier.stats["promotions"] + 4
    assert [eng2.decode_one(5) for _ in range(6)] == cold
    eng2.debug_validate()


# ---------------------------------------------------------------------------
# LCP-linear addressing
# ---------------------------------------------------------------------------

def test_arithmetic_offsets_no_offset_table(small_model):
    """The host arena is LCP-linear: a record's layer page lives at
    ``slot * slot_bytes + layer * layer_stride`` in the flat buffer —
    reconstructing leaves by raw offset arithmetic must agree with the
    store's own unpack, and records carry only a slot index (no
    per-page offset table anywhere in the tier)."""
    cfg, params = small_model
    eng, cache, tier = _tiered_engine(cfg, params)
    prompt = _prompt(33, stride=3)
    eng.add_requests({0: prompt})
    eng.decode_one(0)
    eng.release(0)
    eng.recycle_device_pool()

    assert tier.slot_bytes == cfg.n_layers * tier.layer_stride
    for s in range(tier.host.n_slots):
        assert tier.host.slot_offset(s) == s * tier.slot_bytes
        for li in range(cfg.n_layers):
            assert tier.page_offset(s, li) == \
                s * tier.slot_bytes + li * tier.layer_stride

    flat = tier.host.buf.reshape(-1)
    for rec in tier._records.values():
        assert isinstance(rec.slot, int)       # the only placement state
        leaves, ok = tier.read_record(rec)
        assert ok
        for li in range(cfg.n_layers):
            base = tier.page_offset(rec.slot, li)
            for sp, lf in zip(tier._specs, leaves):
                if not sp.nbytes:
                    continue
                raw = flat[base + sp.offset:base + sp.offset + sp.nbytes]
                want = np.frombuffer(raw.tobytes(), sp.dtype
                                     ).reshape(sp.shape)
                assert np.array_equal(want, lf[li])


def test_disk_spill_roundtrip(small_model, tmp_path):
    """With a disk arena configured, host evictions spill (mmap file)
    instead of dropping, and spilled records still promote verified."""
    cfg, params = small_model
    eng, cache, tier = _tiered_engine(cfg, params, host_mb=0,
                                      disk_dir=str(tmp_path), disk_mb=1)
    assert tier.host.n_slots == 1              # force spills immediately
    prompt = _prompt(33, stride=9)
    eng.add_requests({0: prompt})
    cold = [eng.decode_one(0) for _ in range(4)]
    eng.release(0)
    eng.recycle_device_pool()
    assert tier.stats["spills"] >= 3
    assert tier.stats["drops"] == 0
    assert (tmp_path / "tier_arena.bin").exists()
    levels = {r.level for r in tier._records.values()}
    assert "disk" in levels

    cached = eng.add_requests({1: prompt})[1]
    assert cached == 32
    assert [eng.decode_one(1) for _ in range(4)] == cold
    eng.debug_validate()


# ---------------------------------------------------------------------------
# CAMP eviction/deletion split
# ---------------------------------------------------------------------------

def test_globalcache_evict_cb_sees_victims_order_unchanged():
    """The GlobalCache demotion hook observes every victim while leaving
    eviction order, occupancy, and hit/miss accounting byte-identical
    to the fused evict-and-delete behavior."""
    from repro.core import camp
    plain = camp.GlobalCache(1 << 10, "gcamp", segment=8)
    hooked = camp.GlobalCache(1 << 10, "gcamp", segment=8)
    victims = []
    hooked.evict_cb = lambda blk: victims.append(blk.tag)
    for i in range(600):
        addr, size = i * 64, 8 + (i * 13) % 57
        assert plain.access(addr, size) == hooked.access(addr, size)
    assert victims                                  # evictions happened
    assert all(t not in hooked.blocks for t in victims[-5:])
    assert list(plain.blocks) == list(hooked.blocks)
    assert plain.used_segments == hooked.used_segments
    assert (plain.hits, plain.misses) == (hooked.hits, hooked.misses)


# ---------------------------------------------------------------------------
# multi-turn decode-page caching
# ---------------------------------------------------------------------------

def test_decode_pages_cached_across_turns(small_model):
    """cache_decode_pages=True demotes decode-produced full pages on
    release, so a multi-turn conversation whose turn-2 prompt embeds
    turn 1's reply hits the tier *past* turn 1's prompt-page boundary
    even after a full device-pool recycle — with the promoted decode
    pages bit-identical to the bytes decode originally published."""
    cfg, params = small_model
    eng, cache, tier = _tiered_engine(cfg, params,
                                      cache_decode_pages=True)
    prompt = _prompt(17)                       # 2 stored pages
    eng.add_requests({1: prompt})
    reply = [eng.decode_one(1) for _ in range(16)]
    seq = eng.seqs[1]                          # 33 tokens: 4 full pages
    n_blocks = len(seq.pages[0])
    assert n_blocks == 4 and len(seq.chain) == 2
    decode_bits = [
        [np.stack([np.asarray(lf[seq.pages[li][b]])
                   for li in range(cfg.n_layers)])
         for lf in jax.tree.leaves(eng.pools)]
        for b in range(2, n_blocks)]
    eng.release(1)
    assert tier.stats["demotions"] >= 2        # the two decode blocks
    assert any(r.source == "decode" for r in tier._records.values())
    eng.recycle_device_pool()

    convo2 = prompt + reply + [3, 4, 5]        # 36 tokens, 4 pages cached
    cached = eng.add_requests({2: convo2})[2]
    assert cached == 32                        # past the 16-token boundary
    after = [_entry_page_state(eng, eid)[0]
             for eid in eng.seqs[2].chain[2:]]
    for want, got in zip(decode_bits, after):
        for x, y in zip(want, got):
            assert np.array_equal(x, y)
    eng.decode_one(2)
    eng.release(2)
    eng.debug_validate()
