"""Per-layer metrics read from the program's counters over the window."""

from types import SimpleNamespace

import pytest

from bench.harness import runner


def _rec(**counters):
    return SimpleNamespace(counters=counters)


def test_walk_share_on_recorded_counters():
    """32 layers, 280 decode dispatches of 8 slots at PMAX 128; the active
    rows held 48 pages on average, 4.45 rows a dispatch."""
    read = runner.reader("paged_attention_walk_share")
    got = read(_rec(attn_pages_read=32 * 280 * 4.45 * 48,
                    attn_pages_padded=32 * 280 * 8 * 128,
                    bytes_raw=10, bytes_compressed=5))
    assert got == pytest.approx(100 * 4.45 * 48 / (8 * 128))


def test_walk_share_reads_nothing_without_the_counters():
    read = runner.reader("paged_attention_walk_share")
    assert read(_rec(bytes_raw=10, bytes_compressed=5)) is None
    assert read(_rec(attn_pages_read=0, attn_pages_padded=0)) is None
