"""Shared pytest configuration for the test suite."""

import pytest

from repro.serving.engine import PagedKVEngine


def oracle_view(stats: dict) -> dict:
    """The counters the host-looped oracle mirrors: all but the batched
    engine's decode-attention walk (``PagedKVEngine._WALK_KEYS``)."""
    return {k: v for k, v in stats.items()
            if k not in PagedKVEngine._WALK_KEYS}


def assert_engine_stats_match(a, b, codec):
    """Engine-vs-oracle stats equality, codec-size-stability aware.

    Every counter the oracle mirrors must match exactly.
    ``bytes_compressed`` is the one exception: it sums ``page_nbytes``
    over published pages, and for codecs with ``ulp_stable_sizes =
    False`` (fpc, adaptive) the size function reads exact bit patterns
    — decode-tail KV at layers >= 1 is
    token-pinned but not bit-pinned across the batched engine and the
    op-by-op oracle, so a word can flip between the bf16-exact and
    full-exception classes.  Allow a few bytes of class-flip skew per
    published page there; an actual accounting bug (a page counted
    twice, a dedup reversal missed) is hundreds of bytes and still
    trips the tolerance.
    """
    a, b = oracle_view(a), oracle_view(b)
    if codec.ulp_stable_sizes:
        assert a == b
        return
    ka = {k: v for k, v in a.items() if k != "bytes_compressed"}
    kb = {k: v for k, v in b.items() if k != "bytes_compressed"}
    assert ka == kb
    pages = max(a.get("pages_compressed", 1), 1)
    skew = abs(a["bytes_compressed"] - b["bytes_compressed"])
    assert skew <= 8 * pages, (a["bytes_compressed"], b["bytes_compressed"])


@pytest.fixture
def assert_stats():
    return assert_engine_stats_match


@pytest.fixture
def oracle_stats():
    return oracle_view


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "bf16_tie_sensitive: engine-vs-oracle token comparison whose "
        "workload is known argmax-tie-free only under the default bdi "
        "codec.  Both engines are correct on a tie (two logits within "
        "one bf16 ULP — see serving/engine.py's equivalence caveat); "
        "other codecs shift the logits and may surface one.  The CI "
        "codec-matrix leg deselects these with -m 'not "
        "bf16_tie_sensitive'; the per-codec equivalence contract itself "
        "is pinned tie-free for every codec in tests/test_codecs.py.")
