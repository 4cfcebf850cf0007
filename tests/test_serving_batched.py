"""Equivalence tests: batched device-resident engine vs the seed engine.

The batched hot path (serving/engine.py) must produce token-for-token
identical greedy output to the host-looped seed engine kept in
serving/reference.py — including across page publishes, padded-page-table
growth, and a CAMP preemption forced mid-decode.  Also checks the
tail-fused paged-attention kernel against its dense dequant oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs.registry import get_arch
from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention_tail
from repro.models.api import get_model
from repro.serving.engine import PagedKVEngine
from repro.serving.reference import ReferencePagedKVEngine

PAGE = 8


@pytest.fixture(scope="module")
def small_model():
    cfg = get_arch("yi-6b").reduced(n_layers=2, d_model=64)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


def _pair(cfg, params, n_pool_pages, max_batch=8):
    return (ReferencePagedKVEngine(cfg, params, page_size=PAGE,
                                   n_pool_pages=n_pool_pages),
            PagedKVEngine(cfg, params, page_size=PAGE,
                          n_pool_pages=n_pool_pages, max_batch=max_batch))


@pytest.mark.bf16_tie_sensitive
def test_decode_batch_matches_reference_engine(small_model, assert_stats):
    """Greedy output identical across ragged prompts and page publishes."""
    cfg, params = small_model
    re_, be = _pair(cfg, params, n_pool_pages=96)
    prompts = {0: [5, 9, 2, 7, 11, 3], 1: [4, 4, 8, 1],
               2: list(range(1, 13))}
    for sid, p in prompts.items():
        re_.add_request(sid, p)
        be.add_request(sid, p)

    for step in range(16):
        out = be.decode_batch()
        for sid in prompts:
            assert re_.decode_one(sid) == out[sid], (step, sid)

    assert_stats(re_.stats, be.stats, be.codec)
    assert re_.pool_used_pages() == be.pool_used_pages()


def test_decode_batch_page_table_growth(small_model):
    """Crossing the padded-PMAX doubling boundary keeps outputs identical."""
    cfg, params = small_model
    re_, be = _pair(cfg, params, n_pool_pages=64, max_batch=2)
    prompt = [1 + (j * 5) % (cfg.vocab - 1) for j in range(62)]
    re_.add_request(0, prompt)
    be.add_request(0, prompt)
    assert be._pmax == 8                       # 7 pages/layer after prefill
    for step in range(12):                     # crosses 8 pages -> PMAX 16
        assert re_.decode_one(0) == be.decode_one(0), step
    assert be._pmax == 16
    assert re_.seqs[0].tokens == be.seqs[0].tokens


@pytest.mark.bf16_tie_sensitive
def test_camp_preemption_mid_decode_matches_reference(small_model):
    """A finished request's lingering KV is evicted mid-decode by both.

    Pool sized so tail publishes exhaust it while three live sequences
    decode; the `done` sequence has CAMP value -1 and is deterministically
    the victim in both engines.  Live sequences' greedy tokens must stay
    identical through the preemption.
    """
    cfg, params = small_model
    re_, be = _pair(cfg, params, n_pool_pages=24)
    prompts = {0: [5, 9, 2, 7, 11, 3], 1: [3, 1, 4, 1, 5],
               2: [2, 7, 1, 8, 2, 8], 3: list(range(1, 40))}
    for sid, p in prompts.items():
        re_.add_request(sid, p)
        be.add_request(sid, p)
    re_.seqs[3].done = True
    be.seqs[3].done = True

    live = [0, 1, 2]
    preempt_step = None
    for step in range(20):
        for sid in live:
            re_.decode_one(sid)
        be.decode_batch(live)
        assert re_.seqs[3].preempted == be.seqs[3].preempted, step
        for sid in live:
            assert re_.seqs[sid].tokens == be.seqs[sid].tokens, (step, sid)
        if re_.seqs[3].preempted:
            preempt_step = step
            break
    assert preempt_step is not None, "pool never forced a preemption"
    assert re_.stats["preemptions"] == be.stats["preemptions"] == 1
    assert be.stats["pages_evicted"] > 0

    # decode continues correctly after the eviction freed pages
    for step in range(4):
        for sid in live:
            re_.decode_one(sid)
        be.decode_batch(live)
    for sid in live:
        assert re_.seqs[sid].tokens == be.seqs[sid].tokens


def test_preempted_sequence_is_skipped(small_model):
    cfg, params = small_model
    _, be = _pair(cfg, params, n_pool_pages=96)
    be.add_request(0, [1, 2, 3])
    be.add_request(1, [4, 5, 6])
    be.seqs[1].preempted = True
    out = be.decode_batch()
    assert set(out) == {0}


def test_release_recycles_slot_and_pages(small_model):
    cfg, params = small_model
    _, be = _pair(cfg, params, n_pool_pages=96, max_batch=2)
    be.add_request(0, list(range(1, 13)))      # 12 toks -> 1 page/layer
    be.add_request(1, [4, 5, 6])
    assert not be._free_slots                  # at capacity
    used_before = be.pool_used_pages()
    be.decode_batch()
    be.release(0)
    assert be.pool_used_pages() < used_before  # pages returned to the pool
    be.add_request(2, [7, 8, 9, 10])           # reuses the freed slot
    out = be.decode_batch()
    assert set(out) == {1, 2}


@pytest.mark.bf16_tie_sensitive
def test_chunked_prefill_batched_admission_matches_reference(
        small_model, assert_stats, oracle_stats):
    """One chunked-batch prefill pass == sequential oracle prefill.

    Ragged prompts around the chunk grid (chunk = 2 * PAGE = 16): shorter
    than a page, page-aligned, spanning a chunk boundary (19), and
    multi-chunk (34).  Greedy decode must stay token-for-token identical
    afterwards and CAMP accounting must match exactly.
    """
    cfg, params = small_model
    re_, be = _pair(cfg, params, n_pool_pages=96)
    prompts = {0: [5, 9, 2, 7, 11, 3], 1: list(range(1, 20)),
               2: [4, 4, 8, 1], 3: [1 + (j * 3) % 50 for j in range(34)]}
    re_.add_requests(prompts)
    be.add_requests(prompts)
    for sid in prompts:
        assert re_.seqs[sid].tail_len == be.seqs[sid].tail_len, sid
    assert oracle_stats(re_.stats) == oracle_stats(be.stats)  # prefill
    for step in range(12):
        out = be.decode_batch()
        for sid in prompts:
            assert re_.decode_one(sid) == out[sid], (step, sid)
    assert_stats(re_.stats, be.stats, be.codec)
    assert re_.pool_used_pages() == be.pool_used_pages()


def test_prefill_camp_preemption_mid_prefill(small_model, oracle_stats):
    """A prompt whose prefill exhausts the pool evicts the done victim.

    Seq 0 (done, CAMP value -1) holds 10 pages; seq 1's prefill demands 10
    more from a 14-page pool, forcing one deterministic preemption midway
    through prefill in both engines.  Page counts, byte accounting and
    subsequent greedy decode must match.
    """
    cfg, params = small_model
    re_, be = _pair(cfg, params, n_pool_pages=15)
    # 41 tokens -> 40 stored -> 5 pages x 2 layers (prefill stores every
    # prompt token but the last; decode writes the last one into the tail)
    long_a = [2 + (j * 7) % 40 for j in range(41)]
    long_b = [3 + (j * 5) % 40 for j in range(41)]
    for eng in (re_, be):
        eng.add_request(0, long_a)
        eng.seqs[0].done = True
        eng.add_request(1, long_b)
        assert eng.seqs[0].preempted, "prefill never forced the preemption"
        assert not eng.seqs[1].preempted
    assert oracle_stats(re_.stats) == oracle_stats(be.stats)
    assert re_.stats["preemptions"] == 1
    assert re_.stats["pages_evicted"] == 10
    for step in range(6):
        out = be.decode_batch([1])
        assert re_.decode_one(1) == out[1], step


def test_self_preemption_publish_drops_pages(small_model):
    """CAMP quirk fix: a sequence preempted during its own page publish
    no longer keeps fresh pages attached.

    A lone 72-token prompt needs 18 pages from an 8-page pool, so CAMP's
    only candidate victim mid-prefill is the prefilling sequence itself.
    Both engines must end preempted with zero attached pages and an empty
    pool — pre-fix, post-preemption publishes kept attaching pages that
    leaked until release().
    """
    cfg, params = small_model
    re_, be = _pair(cfg, params, n_pool_pages=9)
    prompt = [1 + (j * 11) % 60 for j in range(72)]
    for eng in (re_, be):
        eng.add_request(0, prompt)
        seq = eng.seqs[0]
        assert seq.preempted
        assert all(not lp for lp in seq.pages), "fresh pages leaked"
        assert seq.tail_len == 0
        assert eng.pool_used_pages() == 0
        assert eng.stats["preemptions"] == 1
    for key in ("preemptions", "pages_evicted", "pages_compressed"):
        assert re_.stats[key] == be.stats[key], key


def test_fused_kernel_engine_matches_fallback(small_model):
    """use_fused=True (Pallas paged-attention + page-fill codec, interpret
    mode on CPU) decodes the same greedy tokens as the jnp fallback."""
    cfg, params = small_model
    base = PagedKVEngine(cfg, params, page_size=PAGE, n_pool_pages=64,
                         max_batch=4, use_fused=False)
    fused = PagedKVEngine(cfg, params, page_size=PAGE, n_pool_pages=64,
                          max_batch=4, use_fused=True)
    prompts = {0: [5, 9, 2, 7, 11, 3], 1: list(range(1, 14))}
    base.add_requests(prompts)
    fused.add_requests(prompts)
    assert base.stats == fused.stats   # codec kernel is bit-exact with ref
    for step in range(4):
        assert base.decode_batch() == fused.decode_batch(), step


def test_gqa_forward_external_kv_projects_once(monkeypatch):
    """gqa_forward(kv=...) must not re-project K/V — the serving engines
    rely on this to hit each projection exactly once per layer."""
    from repro.models import attention as A
    from repro.models import layers as Lmod

    key = jax.random.PRNGKey(0)
    p = A.init_gqa(key, 32, 4, 2, 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 32), jnp.bfloat16)
    pos = jnp.arange(6, dtype=jnp.int32)

    kv = A.gqa_kv(p, x, pos)
    want = A.gqa_forward(p, x, pos)

    calls = []
    real = Lmod.linear
    monkeypatch.setattr(Lmod, "linear",
                        lambda pp, xx: calls.append(1) or real(pp, xx))
    got = A.gqa_forward(p, x, pos, kv=kv)
    assert len(calls) == 1             # wq only; wk/wv came from kv
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# Kernel cases: (kvh, g, d, page, pmax, rows), each row (page tokens, tail
# slots).  yi-6b's and Qwen2.5-14B's head layouts at full head width; a
# PMAX that is not a multiple of the 128-token block; rows with no pages
# (an inactive slot holds one tail slot, as the engine passes it), at
# full PMAX, and ending mid-page and mid-block.
TAIL_CASES = {
    "small": (2, 4, 16, 8, 4, [(2 * 8, 3), (0, 1), (4 * 8, 8)]),
    "yi": (4, 8, 128, 16, 20, [(20 * 16, 16), (0, 3), (0, 1),
                               (9 * 16 + 5, 2)]),
    "qwen": (8, 5, 128, 16, 12, [(12 * 16, 5), (0, 1), (8 * 16, 16),
                                 (3 * 16 + 1, 1)]),
}


@pytest.mark.parametrize("case", list(TAIL_CASES))
def test_paged_attention_tail_matches_ref(case):
    """Tail-fused kernel == dense dequant oracle, on the TPU interpreter.

    Table entries past each row's pages name pool pages that do not
    exist: the interpreter raises on an out-of-bounds read, so passing
    proves the kernel never copies a page past a row's length.  The
    oracle, which reads the whole padded table, gets scrambled in-pool
    pages there instead, which its mask drops."""
    kvh, g, d, page, pmax, rows = TAIL_CASES[case]
    bsz, pool = len(rows), 2 + sum(-(-n // page) for n, _ in rows)
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    q = jax.random.normal(ks[0], (bsz, kvh, g, d))
    k = jax.random.normal(ks[1], (pool, kvh, page, d))
    v = jax.random.normal(ks[2], (pool, kvh, page, d))
    pages = ref.compress_kv_pages(k, v)
    scram = np.array(jax.random.randint(ks[3], (bsz, pmax), 1, pool))
    pt, nxt = np.full((bsz, pmax), pool + 1000, np.int32), 1
    for b, (n, _) in enumerate(rows):
        used = -(-n // page)
        pt[b, :used] = np.arange(nxt, nxt + used)
        scram[b, :used] = pt[b, :used]
        nxt += used
    lengths = jnp.asarray([n for n, _ in rows], jnp.int32)
    tail_k = jax.random.normal(ks[4], (bsz, kvh, page, d))
    tail_v = jax.random.normal(ks[5], (bsz, kvh, page, d))
    tail_len = jnp.asarray([t for _, t in rows], jnp.int32)

    got = paged_attention_tail(q, pages, jnp.asarray(pt), lengths,
                               tail_k, tail_v, tail_len,
                               interpret=pltpu.InterpretParams())
    want = ref.paged_attention_tail_ref(q, pages, jnp.asarray(scram),
                                        lengths, tail_k, tail_v, tail_len)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)

def test_attention_walk_counters(small_model):
    """Each decode dispatch counts the active rows' pages, and the padded
    ``max_batch x PMAX`` table, once per layer."""
    cfg, params = small_model
    eng = PagedKVEngine(cfg, params, page_size=PAGE, n_pool_pages=96,
                        max_batch=4)
    eng.add_requests({0: list(range(1, 20)), 1: [4, 4, 8, 1],
                      2: list(range(3, 45))})
    eng.seqs[2].done = True                   # holds pages, not decoded
    for _ in range(10):
        before = eng.stats
        pages = sum(len(eng.seqs[sid].pages[0]) for sid in (0, 1))
        eng.decode_batch()
        assert (eng.stats["attn_pages_read"] - before["attn_pages_read"]
                == cfg.n_layers * pages)
        assert (eng.stats["attn_pages_padded"]
                - before["attn_pages_padded"]
                == cfg.n_layers * 4 * eng._pmax)
    assert pages > 2                          # a publish happened
