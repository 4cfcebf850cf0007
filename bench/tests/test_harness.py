"""The harness end to end on the CPU at a tiny size: it finds a cell a
test adds as files, warms every shape its window uses, and judges the
served tokens correct against the plain reference."""

import json

import jax
import numpy as np
import pytest

from bench.harness import runner, serve, spec
from bench.tests import tiny


def test_finds_a_cell_added_as_files(tmp_path):
    root = tiny.root(tmp_path, "tiny-chat")
    cell = spec.load_cell("tiny-chat", root)
    assert cell.config["hidden_size"] == 64
    assert cell.mix["classes"][0]["prefix"]["groups"] == 2
    with pytest.raises(FileNotFoundError):
        spec.load_cell("not-there", root)


@pytest.mark.parametrize("name,seed", [("tiny-chat", 2 ** 31 + 17),
                                       ("tiny-reason", 5)])
def test_run_is_correct_with_no_compile_in_window(tmp_path, name, seed,
                                                  capsys):
    out = tiny.run(tmp_path, name, seed)
    json.dumps(out)
    assert out["correct"], out
    assert list(out)[-1] == "checks"
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {"tbt_p95_ms", "output_tok_s", "setup_s"} | (
        {"ttft_p50_ms"} if name == "tiny-chat" else set())
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["checks"]["window_compiles"] == {"value": 0, "limit": 0}
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-3].startswith("check logit_gap:")
    assert err[-2].startswith("check failed_requests:")
    assert err[-1].startswith("check window_compiles:")


def test_a_mix_added_as_data_runs_with_no_compile_in_window(tmp_path):
    """Multi-turn sessions in two classes, in bursts: a mix the benchmark
    does not hold, added as one file, warmed and served whole."""
    root = tiny.root(tmp_path)
    mix = {"arrival": {"kind": "poisson", "rate_per_s": 4.0,
                       "preroll_s": 0.5, "phases": [[0.5, 3.0], [1.0, 0.5]]},
           "classes": [
               {"share": 2.0, "prefix": {"groups": 0, "tokens": 0,
                                         "zipf_s": 1.0},
                "prompt_tokens": {"median": 12, "sigma": 0.5, "min": 4,
                                  "max": 24},
                "output_tokens": {"median": 6, "sigma": 0.5, "min": 2,
                                  "max": 12},
                "turns": 3, "think_s": 0.1},
               {"share": 1.0, "prefix": {"groups": 2, "tokens": 48,
                                         "zipf_s": 1.0},
                "prompt_tokens": {"median": 20, "sigma": 0.5, "min": 8,
                                  "max": 40},
                "output_tokens": {"median": 10, "sigma": 0.5, "min": 4,
                                  "max": 20},
                "turns": 1, "think_s": 0.0}]}
    (root / "traffic" / "sessions.json").write_text(json.dumps(mix))
    cell = dict(tiny.CELLS["tiny-chat"], traffic="sessions")
    (root / "cells" / "tiny-sessions.json").write_text(json.dumps(cell))
    c = spec.load_cell("tiny-sessions", root)
    out = runner.run_cell(c, 8, 3.0, trace_on=False, t_process=0.0,
                          bench=tiny.BENCH, devs=jax.devices(),
                          log=lambda s: None)
    assert out["correct"], out
    assert out["attempted"] > 0 and out["failed"] == 0


def test_sweep_covers_every_count():
    """The warm-up's cohorts publish every page count and write every
    tail count a cohort of their rows and scratch length can."""
    plan = {"page": 16, "chunk": 128, "stored": (527, 895)}
    rng = np.random.default_rng(0)
    for rows in (1, 2, 4, 8):
        got_m, got_w = set(), set()
        for stored, steps in serve.sweep_cohorts(rows, 1024, plan, rng):
            assert len(stored) == rows
            assert serve.tmax_of(max(stored), 128) == 1024
            pub, done, roff = [0] * rows, set(), 0
            for n in steps:
                m, w = serve._advance(stored, pub, done, roff, n, 16)
                roff += n
                got_m.add(m)
                got_w.add(w)
        assert set(range(1, rows * 8 + 1)) <= got_m
        assert set(range(1, rows + 1)) <= got_w


def test_tail_rounds_fill_every_count():
    for b in (1, 4, 6, 8):
        rounds = serve.tail_rounds(b)
        assert all(sum(g) <= b for g in rounds)
        assert sorted(x for g in rounds for x in g) == list(range(1, b + 1))


@pytest.mark.parametrize("stored", [1, 127, 128, 129, 384, 385, 895, 896])
def test_stored_range_inverts_tmax(stored):
    t = serve.tmax_of(stored, 128)
    lo, hi = serve.stored_range(t, 128)
    assert lo <= stored <= hi
