"""Codec registry + per-codec contract suite.

Covers the `PageCodec` seam end to end: registry lookup/error behavior,
per-codec roundtrip contracts (bit-exact identity for lossless codecs,
bounded error + determinism for bdi), device-side byte accounting
(zero-page credit, raw == raw-size), and a parametrized engine/oracle
token-equivalence + warm==cold smoke across every registered codec —
the "any compression algorithm fits LCP" claim, pinned.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import codecs
from repro.configs.registry import get_arch
from repro.models.api import get_model
from repro.serving.engine import PagedKVEngine
from repro.serving.prefix_cache import PrefixCache
from repro.serving.reference import ReferencePagedKVEngine

PAGE = 8
ALL_CODECS = codecs.available()


@pytest.fixture(scope="module")
def small_model():
    cfg = get_arch("yi-6b").reduced(n_layers=2, d_model=64)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


def _pages(key, n=4, kvh=2, page=PAGE, d=16):
    """KV page blocks mixing the interesting row classes: random rows,
    exact-zero rows, and repeated-value rows."""
    k1, k2 = jax.random.split(key)
    k = jax.random.normal(k1, (n, kvh, page, d))
    v = jax.random.normal(k2, (n, kvh, page, d))
    k = k.at[0, 0, 0].set(0.0)                     # one all-zero row
    k = k.at[0, 0, 1].set(2.5)                     # one repeated-value row
    v = v.at[1].set(0.0)                           # an all-zero page side
    return k, v


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_has_all_builtins():
    assert {"bdi", "zero", "raw", "gbdi", "fpc", "adaptive"} \
        <= set(ALL_CODECS)


def test_registry_returns_singletons():
    for name in ALL_CODECS:
        c = codecs.get(name)
        assert c is codecs.get(name)               # jit traces stay shared
        assert c.name == name
        assert codecs.resolve(name) is c
        assert codecs.resolve(c) is c


def test_registry_unknown_name_lists_available():
    with pytest.raises(KeyError, match="unknown page codec 'nope'"):
        codecs.get("nope")
    with pytest.raises(KeyError, match="bdi"):
        codecs.get("nope")


def test_default_resolution_honors_env(monkeypatch):
    monkeypatch.delenv("REPRO_CODEC", raising=False)
    assert codecs.resolve(None).name == "bdi"
    monkeypatch.setenv("REPRO_CODEC", "raw")
    assert codecs.resolve(None).name == "raw"


def test_reregistering_name_with_new_instance_rejected():
    with pytest.raises(AssertionError):
        codecs.register(codecs.RawCodec())         # fresh instance, old name
    codecs.register(codecs.RAW)                    # same instance: idempotent


# ---------------------------------------------------------------------------
# roundtrip contracts
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ALL_CODECS)
def test_roundtrip_contract(name):
    """Lossless codecs roundtrip bit-exactly; bdi stays inside its
    scale/2 error bound.  Both must be deterministic (two compressions
    of the same data produce identical bits — the canonical-prefix
    contract rests on this)."""
    codec = codecs.get(name)
    k, v = _pages(jax.random.PRNGKey(3))
    pg = codec.compress_kv_pages(k, v)
    pg2 = codec.compress_kv_pages(k, v)
    for a, b in zip(jax.tree.leaves(pg), jax.tree.leaves(pg2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    kr, vr = codec.decompress_pages(pg)
    if codec.lossless:
        np.testing.assert_array_equal(np.asarray(kr), np.asarray(k))
        np.testing.assert_array_equal(np.asarray(vr), np.asarray(v))
    # canonical_roundtrip must agree bit-for-bit with
    # decompress(compress(...)) — it is the same function by contract
    krt, vrt = codec.canonical_roundtrip(k, v)
    np.testing.assert_array_equal(np.asarray(kr), np.asarray(krt))
    np.testing.assert_array_equal(np.asarray(vr), np.asarray(vrt))


def test_bdi_roundtrip_error_bound():
    codec = codecs.get("bdi")
    k, v = _pages(jax.random.PRNGKey(5))
    pg = codec.compress_kv_pages(k, v)
    kr, _ = codec.decompress_pages(pg)
    bound = np.asarray(pg.ks)[..., None]           # per-row scale
    assert np.all(np.abs(np.asarray(kr - k)) <= 0.5 * bound + 1e-7)


@pytest.mark.parametrize("name", ALL_CODECS)
def test_zero_pages_roundtrip_exact(name):
    """The all-zero page (LCP's headline case) roundtrips exactly under
    every codec."""
    codec = codecs.get(name)
    z = jnp.zeros((2, 2, PAGE, 16))
    kr, vr = codec.canonical_roundtrip(z, z)
    np.testing.assert_array_equal(np.asarray(kr), 0.0)
    np.testing.assert_array_equal(np.asarray(vr), 0.0)


# ---------------------------------------------------------------------------
# byte accounting
# ---------------------------------------------------------------------------

def test_page_nbytes_shapes_and_positivity():
    k, v = _pages(jax.random.PRNGKey(7))
    for name in ALL_CODECS:
        codec = codecs.get(name)
        nb = codec.page_nbytes(codec.compress_kv_pages(k, v))
        assert nb.shape == (k.shape[0],) and nb.dtype == jnp.int32
        assert bool(jnp.all(nb > 0))


def test_raw_codec_reports_raw_size():
    """compressed == raw: the engine-visible ratio must be exactly 1."""
    codec = codecs.get("raw")
    k, v = _pages(jax.random.PRNGKey(9))
    nb = codec.page_nbytes(codec.compress_kv_pages(k, v))
    kvh, page, d = k.shape[1:]
    raw = 2 * kvh * page * d * 2                   # K+V sides, bf16 elems
    assert np.all(np.asarray(nb) == raw)


def test_zero_codec_zero_pages_are_tiny():
    codec = codecs.get("zero")
    kvh, page, d = 2, PAGE, 16
    z = jnp.zeros((1, kvh, page, d))
    r = jax.random.normal(jax.random.PRNGKey(1), (1, kvh, page, d))
    nb_zero = int(codec.page_nbytes(codec.compress_kv_pages(z, z))[0])
    nb_rand = int(codec.page_nbytes(codec.compress_kv_pages(r, r))[0])
    assert nb_zero == 2 * kvh * page               # 1 flag byte per row
    assert nb_zero < nb_rand / 10                  # near-free zero pages


def test_bdi_zero_rows_earn_size_credit():
    codec = codecs.get("bdi")
    kvh, page, d = 2, PAGE, 16
    r = jax.random.normal(jax.random.PRNGKey(2), (1, kvh, page, d))
    z = jnp.zeros_like(r)
    nb_rand = int(codec.page_nbytes(codec.compress_kv_pages(r, r))[0])
    nb_zero = int(codec.page_nbytes(codec.compress_kv_pages(z, z))[0])
    assert nb_zero == 2 * 8 * kvh * page           # metadata only
    assert nb_zero < nb_rand


# ---------------------------------------------------------------------------
# engine/oracle equivalence + warm==cold, per codec
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ALL_CODECS)
def test_engine_oracle_equivalence_per_codec(small_model, name,
                                             assert_stats, oracle_stats):
    """Token-for-token greedy equivalence (and exact CAMP byte
    accounting) between the batched engine and the host-looped oracle
    under every registered codec."""
    cfg, params = small_model
    re_ = ReferencePagedKVEngine(cfg, params, page_size=PAGE,
                                 n_pool_pages=96, codec=name)
    be = PagedKVEngine(cfg, params, page_size=PAGE, n_pool_pages=96,
                       max_batch=8, codec=name)
    prompts = {0: [5, 9, 2, 7, 11, 3], 1: list(range(1, 20))}
    re_.add_requests({k: list(v) for k, v in prompts.items()})
    be.add_requests({k: list(v) for k, v in prompts.items()})
    assert oracle_stats(re_.stats) == oracle_stats(be.stats)
    for step in range(8):
        out = be.decode_batch()
        for sid in prompts:
            assert re_.decode_one(sid) == out[sid], (name, step, sid)
    assert_stats(re_.stats, be.stats, be.codec)
    if be.codec.ulp_stable_sizes:
        assert re_.request_bytes == be.request_bytes
    else:
        # raw bytes exact; compressed bytes skew-tolerant (decode-tail
        # bits are token-pinned, not bit-pinned, across the engines)
        assert re_.request_bytes.keys() == be.request_bytes.keys()
        for sid, (raw_r, comp_r) in re_.request_bytes.items():
            raw_b, comp_b = be.request_bytes[sid]
            assert raw_r == raw_b, sid
            assert abs(comp_r - comp_b) <= 64, sid
    if name == "raw":
        assert be.compression_ratio() == 1.0       # LCP exception story


@pytest.mark.parametrize("name", ALL_CODECS)
def test_warm_equals_cold_per_codec(small_model, name):
    """The prefix-cache canonical contract holds under every codec: a
    warm request mapping cached pages decodes bit-identically to a cold
    run."""
    cfg, params = small_model
    prompt = [1 + (j * 3) % 50 for j in range(34)]      # 33 stored: 4 pages
    cache = PrefixCache.for_model(cfg, PAGE)
    eng = PagedKVEngine(cfg, params, page_size=PAGE, n_pool_pages=96,
                        max_batch=4, prefix_cache=cache, codec=name)
    eng.add_requests({0: list(prompt)})
    cold = [eng.decode_batch([0])[0] for _ in range(8)]
    eng.release(0)

    starts = eng.begin_cohort({1: list(prompt)})
    assert starts == {1: 32}, (name, starts)
    while eng._cohort is not None:
        eng.mixed_step(decode_sids=[], pf_tokens=eng.prefill_chunk)
    warm = [eng.decode_batch([1])[1] for _ in range(8)]
    assert warm == cold, name


def test_lossless_flags():
    """The identity fast path is keyed off these; pin them."""
    assert not codecs.get("bdi").lossless
    assert codecs.get("zero").lossless
    assert codecs.get("raw").lossless
    assert not codecs.get("gbdi").lossless          # int8/int4 quantization
    assert codecs.get("fpc").lossless               # bit-pattern coding
    assert not codecs.get("adaptive").lossless      # lossy members can win
    assert codecs.get("bdi").has_fused_kernels
    assert not codecs.get("raw").has_fused_kernels
    # fill-only fused paths: a Pallas page-fill compressor without a
    # fused attention kernel
    assert codecs.get("gbdi").has_fused_fill
    assert not codecs.get("gbdi").has_fused_kernels
    assert codecs.get("adaptive").has_fused_fill
    assert not codecs.get("adaptive").has_fused_kernels


def test_engine_downgrades_use_fused_for_kernel_less_codec(small_model):
    """use_fused=True with a codec that ships no fused kernels falls
    back to the generic path instead of crashing."""
    cfg, params = small_model
    eng = PagedKVEngine(cfg, params, page_size=PAGE, n_pool_pages=32,
                        max_batch=2, use_fused=True, codec="raw")
    assert not eng.use_fused
    assert not eng.use_fused_fill
    eng.add_request(0, [1, 2, 3, 4, 5])
    assert isinstance(eng.decode_one(0), int)


def test_engine_routes_fused_fill_without_fused_attention(small_model):
    """A fill-only fused codec (gbdi) gets ``use_fused_fill`` while the
    attention path stays on the gather-dequant fallback — and the fused
    publish writes bit-identical pool state (pinned via the publish
    checksums, which hash the compressed bytes)."""
    cfg, params = small_model
    prompts = {0: [5, 9, 2, 7, 11, 3, 8, 4, 6, 1]}
    engines = []
    for fused in (False, True):
        eng = PagedKVEngine(cfg, params, page_size=PAGE, n_pool_pages=32,
                            max_batch=2, use_fused=fused, codec="gbdi")
        assert not eng.use_fused
        assert eng.use_fused_fill == fused
        eng.add_requests({k: list(v) for k, v in prompts.items()})
        engines.append(eng)
    ref_eng, fused_eng = engines
    np.testing.assert_array_equal(ref_eng.page_checksum,
                                  fused_eng.page_checksum)
    np.testing.assert_array_equal(ref_eng.page_bytes, fused_eng.page_bytes)
    for _ in range(4):
        assert ref_eng.decode_one(0) == fused_eng.decode_one(0)


# ---------------------------------------------------------------------------
# gbdi: multi-base B+Delta
# ---------------------------------------------------------------------------

def test_gbdi_kernel_oracle_parity():
    """The Pallas compress/decompress pair is bit-exact with the jnp
    oracle (same shared per-page function; pinned here so interpret-mode
    CI catches any drift in either body)."""
    from repro.kernels import ops
    codec = codecs.get("gbdi")
    k, v = _pages(jax.random.PRNGKey(11))
    ref_pg = codec.compress_kv_pages(k, v)
    fus_pg = ops.gbdi_compress_kv_pages(k, v, interpret=True)
    for field, a, b in zip(ref_pg._fields, jax.tree.leaves(ref_pg),
                           jax.tree.leaves(fus_pg)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), field)
    kd, vd = ops.gbdi_decompress_kv_pages(ref_pg, interpret=True)
    kr, vr = codec.decompress_pages(ref_pg)
    np.testing.assert_array_equal(np.asarray(kd), np.asarray(kr))
    np.testing.assert_array_equal(np.asarray(vd), np.asarray(vr))


def test_gbdi_roundtrip_error_bound():
    """|err| <= scale/2 per row, same contract shape as bdi's."""
    codec = codecs.get("gbdi")
    k, v = _pages(jax.random.PRNGKey(13))
    pg = codec.compress_kv_pages(k, v)
    kr, vr = codec.decompress_pages(pg)
    for x, xr, sc in ((k, kr, pg.ksc), (v, vr, pg.vsc)):
        bound = np.asarray(sc)[..., None]
        assert np.all(np.abs(np.asarray(xr - x)) <= 0.5 * bound + 1e-7)


def test_gbdi_byte_accounting():
    """Zero pages cost bases + row metadata only; mixed-content pages
    undercut bdi (2-byte packed row metadata vs bdi's 8-byte base+scale
    pair, minus the K*4-byte page bases)."""
    from repro.kernels.gbdi_codec import K_BASES
    gbdi, bdi = codecs.get("gbdi"), codecs.get("bdi")
    kvh, page, d = 2, PAGE, 16
    z = jnp.zeros((1, kvh, page, d))
    nb_zero = int(gbdi.page_nbytes(gbdi.compress_kv_pages(z, z))[0])
    assert nb_zero == 2 * (K_BASES * 4 + 2 * kvh * page)
    k, v = _pages(jax.random.PRNGKey(17))
    nb_g = np.asarray(gbdi.page_nbytes(gbdi.compress_kv_pages(k, v)))
    nb_b = np.asarray(bdi.page_nbytes(bdi.compress_kv_pages(k, v)))
    assert np.all(nb_g < nb_b)


def test_gbdi_width_classes_fire():
    """The hybrid page/row scale makes the 4-bit width reachable: rows
    tight relative to the page's dynamic range tag wid=1 and drop to
    ceil(D/2) data bytes; constant rows tag wid=0 and drop to none."""
    kvh, page, d = 1, PAGE, 16
    x = jnp.zeros((kvh, page, d))
    # every row anchors at 0 (element 0 stays 0), so page scale is set
    # by the wide row: ps = pow2(8/127) = 1/8, 4-bit threshold 7/8
    x = x.at[0, 0, 1:].set(jnp.linspace(-8.0, 8.0, d - 1))  # wid 2 row
    x = x.at[0, 1:4, 1:].set(0.3)     # fits 4-bit at the page scale
    codec = codecs.get("gbdi")
    pg = codec.compress_kv_pages(x[None], x[None])
    wids = set(np.asarray(pg.kwid).ravel().tolist())
    assert {0, 1, 2} <= wids, wids
    # accounting honors the width classes: cheaper than all-rows-8-bit
    from repro.kernels.gbdi_codec import K_BASES
    all8 = 2 * (K_BASES * 4 + 2 * kvh * page + kvh * page * d)
    assert int(codec.page_nbytes(pg)[0]) < all8


# ---------------------------------------------------------------------------
# fpc: frequent-pattern coding
# ---------------------------------------------------------------------------

def test_fpc_byte_accounting():
    """2 prefix bits per word; zero/repeat words are prefix-only, bf16
    words carry 16 payload bits, exceptions 32."""
    codec = codecs.get("fpc")
    kvh, page, d = 2, PAGE, 16
    words = kvh * page * d
    z = jnp.zeros((1, kvh, page, d))
    nb_zero = int(codec.page_nbytes(codec.compress_kv_pages(z, z))[0])
    assert nb_zero == 2 * ((2 * words + 7) // 8)
    # bf16-exact content: 18 bits/word except repeat chains cost less
    bf = jax.random.normal(jax.random.PRNGKey(23), (1, kvh, page, d))
    bf = bf.astype(jnp.bfloat16).astype(jnp.float32)
    nb_bf = int(codec.page_nbytes(codec.compress_kv_pages(bf, bf))[0])
    assert nb_bf <= 2 * ((18 * words + 7) // 8)
    # dense f32: ~34 bits/word, honest loss vs raw's bf16 accounting
    r = jax.random.normal(jax.random.PRNGKey(29), (1, kvh, page, d))
    r = r + jnp.float32(1e-7) * jax.random.normal(
        jax.random.PRNGKey(31), (1, kvh, page, d))
    nb_r = int(codec.page_nbytes(codec.compress_kv_pages(r, r))[0])
    assert nb_r > int(codecs.get("raw").page_nbytes(
        codecs.get("raw").compress_kv_pages(r, r))[0])


def test_fpc_bit_exact_on_edge_patterns():
    """-0.0 is NOT the zero class (bit pattern 0x80000000) and must
    round-trip bit-exactly; repeat detection is bit-equality."""
    codec = codecs.get("fpc")
    kvh, page, d = 1, PAGE, 8
    x = jnp.zeros((1, kvh, page, d))
    x = x.at[0, 0, 0, 0].set(-0.0)
    x = x.at[0, 0, 1].set(1.5)                      # repeat run
    x = x.at[0, 0, 2, ::2].set(jnp.float32(0.1))    # non-bf16 exceptions
    kr, vr = codec.canonical_roundtrip(x, x)
    bits = lambda a: np.asarray(a).view(np.uint32)  # noqa: E731
    np.testing.assert_array_equal(bits(kr), bits(x))
    np.testing.assert_array_equal(bits(vr), bits(x))


# ---------------------------------------------------------------------------
# adaptive: per-page codec selection
# ---------------------------------------------------------------------------

def test_adaptive_tag_is_first_pool_leaf():
    """faults.corrupt_page flips a bit in the first nonempty pool leaf
    and the snapshot dump names leaves in flatten order; both rely on
    the tag leading the pytree."""
    from repro.codecs.adaptive import AdaptiveKVPages
    assert AdaptiveKVPages._fields[0] == "tag"


def test_adaptive_picks_smallest_per_page():
    """Per page: tag == first-smallest member, accounted bytes == that
    member's bytes + the 1-byte tag."""
    codec = codecs.get("adaptive")
    k, v = _pages(jax.random.PRNGKey(37))
    pg = codec.compress_kv_pages(k, v)
    sizes = np.stack([m.page_nbytes(c) for m, c in
                      zip(codec.members, codec._member_pages(pg))])
    tags = np.asarray(codec.page_tags(pg))
    np.testing.assert_array_equal(tags, np.argmin(sizes, axis=0))
    np.testing.assert_array_equal(np.asarray(codec.page_nbytes(pg)),
                                  sizes.min(axis=0) + 1)
    # the all-zero page side (v[1] in the fixture) must elect the zero
    # codec; a random page must not
    assert codec.member_names[tags[1]] == "zero" or sizes[:, 1].min() \
        < sizes[codec.member_names.index("zero"), 1]


def _zeroed_embed(params, tok: int):
    """Model-surgery helper: zero one embedding row.  With RMSNorm (no
    additive bias), RoPE(0)=0 and bias-free projections, a prompt run of
    ``tok`` produces exactly-zero K/V rows at every layer — real
    zero-heavy page content, not synthetic pool writes."""
    p = dict(params)
    p["embed"] = {"w": params["embed"]["w"].at[tok].set(0)}
    return p


def test_adaptive_neighbor_pages_differ_in_codec(small_model):
    """A zero-heavy page and its dense neighbor in the same chain elect
    different codecs; the prefix-cache entries record the per-page ids
    and the engine/oracle tag tables agree."""
    cfg, params = small_model
    ztok = cfg.vocab - 2
    p2 = _zeroed_embed(params, ztok)
    prompt = [ztok] * PAGE + [5, 9, 2, 7, 11, 3, 8, 4, 6]   # 2 full pages
    cache = PrefixCache.for_model(cfg, PAGE)
    eng = PagedKVEngine(cfg, p2, page_size=PAGE, n_pool_pages=64,
                        max_batch=2, prefix_cache=cache, codec="adaptive")
    re_ = ReferencePagedKVEngine(cfg, p2, page_size=PAGE, n_pool_pages=64,
                                 codec="adaptive")
    eng.add_requests({0: list(prompt)})
    re_.add_requests({0: list(prompt)})
    seq = eng.seqs[0]
    assert len(seq.pages[0]) == 2
    ids = [int(eng.page_codec_id[pid]) for pid in seq.pages[0]]
    zero_id = codecs.ADAPTIVE.member_names.index("zero")
    assert ids[0] == zero_id and ids[1] != zero_id, ids
    ref_ids = [int(re_.page_codec_id[pid]) for pid in re_.seqs[0].pages[0]]
    assert ref_ids == ids
    # the cache chain records per-layer codec ids, nbytes post-selection
    for blk, eid in enumerate(seq.chain):
        ent = cache.entries[eid]
        assert ent.codec_ids == [int(eng.page_codec_id[p])
                                 for p in ent.pages]
        assert ent.nbytes == sum(int(eng.page_bytes[p]) for p in ent.pages)


def test_adaptive_tags_persist_across_snapshot_restore(small_model,
                                                       tmp_path):
    """page_codec_id and the tag pool leaf survive snapshot/restore, and
    the restored engine keeps decoding token-identically."""
    from repro.serving.snapshot import restore_snapshot, save_snapshot
    cfg, params = small_model
    ztok = cfg.vocab - 2
    p2 = _zeroed_embed(params, ztok)
    prompt = [ztok] * PAGE + [5, 9, 2, 7, 11, 3, 8, 4, 6]
    eng = PagedKVEngine(cfg, p2, page_size=PAGE, n_pool_pages=64,
                        max_batch=2, codec="adaptive")
    eng.add_requests({0: list(prompt)})
    eng.decode_batch()
    save_snapshot(str(tmp_path), eng, None, step=0)
    eng2, _ = restore_snapshot(str(tmp_path), cfg, p2)
    assert eng2.codec.name == "adaptive"
    np.testing.assert_array_equal(eng.page_codec_id, eng2.page_codec_id)
    assert len(set(eng.page_codec_id[np.asarray(eng.seqs[0].pages[0])])) > 1
    np.testing.assert_array_equal(np.asarray(eng.pools.tag),
                                  np.asarray(eng2.pools.tag))
    for _ in range(4):
        assert eng.decode_one(0) == eng2.decode_one(0)


def test_adaptive_corrupt_tag_detected(small_model):
    """A flipped tag bit is caught by the page-integrity checksums: the
    tag is the first pool leaf, so faults.corrupt_page lands on it."""
    from repro.serving import faults as F
    cfg, params = small_model
    eng = PagedKVEngine(cfg, params, page_size=PAGE, n_pool_pages=32,
                        max_batch=2, codec="adaptive")
    eng.add_requests({0: list(range(1, 18))})
    li, pid = 0, eng.seqs[0].pages[0][0]
    pairs = [(li, pid)]
    assert F.verify_pages(eng, pairs).all()
    tag_before = int(np.asarray(eng.pools.tag)[pid])
    inj = F.FaultInjector(F.FaultSpec(), seed=0)
    inj.corrupt_page(eng, li, pid, bit=0)           # first leaf == tag
    assert int(np.asarray(eng.pools.tag)[pid]) == tag_before ^ 1
    assert not F.verify_pages(eng, pairs).all()
    assert not F.verify_seq(eng, 0)


def test_resolve_unknown_env_codec_names_the_env_var(monkeypatch):
    """A bad REPRO_CODEC used to surface as a bare KeyError deep inside
    engine construction; the resolver must name the env var and list
    what is registered."""
    monkeypatch.setenv("REPRO_CODEC", "gzip")
    with pytest.raises(KeyError, match="REPRO_CODEC='gzip'") as ei:
        codecs.resolve(None)
    for name in ALL_CODECS:
        assert name in str(ei.value)
