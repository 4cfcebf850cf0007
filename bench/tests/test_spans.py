"""The program's host spans beside the device's busy time: the traced
window, the idle split by innermost span and the four readers of the
spans, on a small recorded row set whose values were worked by hand
(times in the file are microseconds × 1000, so in ns)."""

import json
import pathlib
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import TraceAnnotation

from bench.harness import runner, spans, trace

ROWS = pathlib.Path(__file__).resolve().parent / "data" / "span_rows.json"
US = 1e3


def _rows():
    return json.loads(ROWS.read_text())


@pytest.fixture
def window(monkeypatch):
    w = spans.Window(_rows())
    monkeypatch.setattr(spans, "of", lambda rec: w)
    return w


def test_window_keeps_the_traced_slice(window):
    assert (window.lo, window.hi) == (0.0, 1000 * US)
    steps = window.named(spans.STEP)
    # names cut at the metadata suffix; the step after the slice is out
    assert [(a / US, b / US) for _, a, b in steps] == \
        [(12, 398), (427, 895), (932, 958)]
    assert [s[1] / US for s in window.dispatched_steps()] == [12, 427]
    # ops merged and clipped: [0,16] [45,55] [125,280] [322,332]
    # [445,840] [990,1000]
    assert window.busy(window.lo, window.hi) == pytest.approx(596 * US)
    assert window.idle(270 * US, 330 * US) == pytest.approx(42 * US)


def test_idle_split_by_innermost_span(window):
    split = {k: v / US for k, v in window.idle_split().items()}
    # [280,322] straddles the token read's end, the publish's start and
    # its own sync's start: 20 + 20 + 2
    assert split == pytest.approx({
        "sched.admit": 4 + 5 + 1, "engine.begin_cohort": 2 + 2 + 35,
        "prefix_cache.lookup": 8, "engine.verify": 8 + 2,
        "engine.sync": 5 + 3 + 20 + 2 + 8 + 10,
        "engine.prepare": 5 + 2 + 10, "engine.page_table": 13,
        "engine.dispatch": 5 + 5, "engine.publish": 20 + 20,
        "sched.step": 2 + 8 + 1 + 1 + 10 + 15 + 26,
        "sched.retire": 28 + 20, "bench.step": 2 + 2 + 5 + 2 + 2,
        "bench.record": 20 + 30, "bench.submit": 5, "bench.wait": 30})
    assert sum(split.values()) == pytest.approx(1000 - 596)


def test_report_covers_step_idle_and_token_reads(window):
    r = spans.report(window)
    assert r["idle_ms"] == pytest.approx(0.404)
    # idle inside sched.step: 306, of which 63 in its own time
    assert r["step_idle_child_share"] == pytest.approx(1 - 63 / 306)
    # token reads end 20 and 10 after their decode programs
    assert window.token_sync_lags() == pytest.approx([20 * US, 10 * US])
    assert r["lag_within_1ms_share"] == 1.0
    assert (r["steps"], r["dispatched"], r["token_syncs"]) == (3, 2, 2)
    assert r["step_idle_ms_min_median_max"] == \
        pytest.approx([0.073, 0.140, 0.207])
    assert (r["stall_steps"], r["stall_idle_ms"]) == (0, 0.0)
    # both programs start 5 after their dispatch starts
    assert r["start_after_dispatch_ms_min_median_max"] == \
        pytest.approx([0.005, 0.005, 0.005])
    # the offset lies between the largest program end less read end
    # (840 - 850) and the least program start less dispatch start (5)
    assert r["clock_offset_bounds_ms"] == pytest.approx([-0.010, 0.005])
    # dispatch start to read end less the program: 180 - 155, 410 - 395
    assert r["round_trip_ms_min_median_max"] == \
        pytest.approx([0.015, 0.020, 0.025])


def test_programs_timed_before_their_dispatch_bound_the_offset():
    # the device's times read 30 early: each program's start falls before
    # its dispatch's start, and is still matched to its step
    rows = [r[:3] + [r[3] - 30 * US] + r[4:]
            if r[0].startswith("/device:") else r for r in _rows()]
    w = spans.Window(rows)
    assert [m[1] / US for _, _, m in w.decode_programs()] == [95, 415]
    assert w.token_sync_lags() == pytest.approx([50 * US, 40 * US])
    lo, hi = w.clock_bounds()
    assert (lo / US, hi / US) == pytest.approx((-40, -25))
    # told the offset, the window puts the device's times back
    back = spans.Window(rows, offset_ns=-30 * US)
    assert back.idle_split() == pytest.approx(spans.Window(_rows())
                                              .idle_split())
    assert back.token_sync_lags() == pytest.approx([20 * US, 10 * US])


def test_bracket_splits_at_both_edges_of_the_offset_bounds():
    out = spans.bracket(_rows())
    assert out["clock_offset_bounds_ms"] == pytest.approx([-0.010, 0.005])
    at_lo, at_hi = out["split_ms_at_offset_bounds"]
    # device times 10 later, busy [0,26] [55,65] [135,290] [332,342]
    # [455,850]: idle in the reads [40,58] [130,300] [320,340] [450,850]
    # is 15 + 15 + 12 + 5 (48 as recorded)
    assert out["split_ms"]["engine.sync"] == pytest.approx(0.048)
    assert at_lo["engine.sync"] == pytest.approx(0.047)
    # 5 earlier, busy [0,11] [40,50] [120,275] [317,327] [440,835]
    # [985,1000]: 8 + 25 + 13 + 15
    assert at_hi["engine.sync"] == pytest.approx(0.061)
    for split in (at_lo, at_hi):
        assert sum(split.values()) == pytest.approx(0.404)


def test_stalls_are_idles_over_ten_times_the_median():
    assert spans.stalls([]) == []
    assert spans.stalls([1.0, 2.0, 3.0, 25.0]) == []
    assert spans.stalls([1.0, 2.0, 3.0, 40.0]) == [40.0]


def test_medians_follow_the_steady_steps(monkeypatch):
    # a third step dispatches (its idle is 26) and publishes twice (5
    # and 7): the medians take the middle step, the means would not
    rows = _rows() + [
        ["/host:CPU", "python3", "engine.dispatch", 940 * US, 2 * US],
        ["/host:CPU", "python3", "engine.publish", 943 * US, 5 * US],
        ["/host:CPU", "python3", "engine.publish", 950 * US, 7 * US]]
    w = spans.Window(rows)
    monkeypatch.setattr(spans, "of", lambda rec: w)
    read = lambda name: runner.reader(name)(SimpleNamespace())
    assert read("scheduler.host_exposed_ms") == pytest.approx(0.073)
    assert read("engine.publish_ms") == pytest.approx(0.007)


@pytest.mark.parametrize("name,want", [
    # idle inside [12,398] is 29 + 70 + 42 + 66, inside [427,895] 18 + 55;
    # the median of two is their mean
    ("scheduler.host_exposed_ms", (207 + 73) / 2 / 1e3),
    # three reads in the first step (the hit's check, the token, the
    # publish), one in the second
    ("engine.syncs_per_step", 2.0),
    ("engine.publish_ms", 60 / 1e3),
    # the admission with a cohort; the empty one at 428 does not count
    ("scheduler.admit_ms", 86 / 1e3),
])
def test_readers_on_recorded_rows(window, name, want):
    assert runner.reader(name)(SimpleNamespace()) == pytest.approx(want)


@pytest.mark.parametrize("name", ["scheduler.host_exposed_ms",
                                  "engine.syncs_per_step",
                                  "engine.publish_ms", "scheduler.admit_ms"])
def test_readers_read_nothing_without_program_spans(monkeypatch, name):
    harness_only = [r for r in _rows() if not r[0].startswith("/host")
                    or r[2].startswith("bench.")]
    w = spans.Window(harness_only)
    monkeypatch.setattr(spans, "of", lambda rec: w)
    assert runner.reader(name)(SimpleNamespace()) is None
    monkeypatch.setattr(spans, "of", lambda rec: None)
    assert runner.reader(name)(SimpleNamespace()) is None


def test_reads_a_profiler_trace_once(tmp_path, monkeypatch):
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation(trace.SLICE):
        with TraceAnnotation(spans.STEP, iteration=3):
            with TraceAnnotation(spans.SYNC):
                jnp.ones(8).block_until_ready()
        with TraceAnnotation("other.span"):
            pass
    jax.profiler.stop_trace()
    monkeypatch.setattr(runner, "TRACE_DIR", tmp_path)
    rec = SimpleNamespace(trace={})
    w = spans.of(rec)
    assert [s[0] for s in w.spans] == [spans.STEP, spans.SYNC]
    assert w.inside(w.spans[0], spans.SYNC) == [w.spans[1]]
    assert spans.of(rec) is w                 # read once per trace file
    assert spans.of(SimpleNamespace(trace=None)) is None
