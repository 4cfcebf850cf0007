"""``correct`` comes out false when the timed path is broken underneath,
and the float8 control fails the limit that sound runs pass."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import correct, runner, serve, spec
from bench.tests import tiny
from repro.serving import engine as E


def test_a_token_altered_where_it_is_produced(tmp_path, monkeypatch):
    orig = E.PagedKVEngine._decode_post
    vocab = 512

    def post(self, sids, nxt):
        nxt = np.array(nxt)
        for sid in sids:             # each request's third token is wrong
            s = self.seqs[sid]
            if len(s.tokens) - len(self.request_prompt[sid]) == 2:
                nxt[s.slot] = (nxt[s.slot] + 1) % vocab
        return orig(self, sids, nxt)

    orig_add = E.PagedKVEngine.begin_cohort

    def begin(self, prompts):
        self.__dict__.setdefault("request_prompt", {}).update(prompts)
        return orig_add(self, prompts)

    monkeypatch.setattr(E.PagedKVEngine, "_decode_post", post)
    monkeypatch.setattr(E.PagedKVEngine, "begin_cohort", begin)
    out = tiny.run(tmp_path, "tiny-chat", 3)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > 0.08


def test_kv_pages_never_written(tmp_path, monkeypatch):
    orig = E._publish_blocks

    def dropped(pools, *a, **kw):
        _, *rest = orig(jax.tree.map(jnp.copy, pools), *a, **kw)
        return (pools, *rest)

    monkeypatch.setattr(E, "_publish_blocks", dropped)
    out = tiny.run(tmp_path, "tiny-reason", 4)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > 0.08


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_float8_control_fails_the_limit(tmp_path, seed):
    """Sound runs pass the limit; the float8 reference put in the served
    tokens' place, through the run's own check, does not."""
    cell = spec.load_cell("tiny-reason", tiny.root(tmp_path, "tiny-reason"))
    rec = runner.serve_window(cell, seed, 2.0, trace_on=False,
                              t_process=0.0, devs=jax.devices(),
                              log=lambda s: None)
    limit = cell.cell["check"]["logit_gap_limit"]
    sound = runner.check(cell, seed, rec, 0, log=lambda s: None)
    ctl = runner.check(cell, seed, rec, 0, log=lambda s: None, control=True)
    assert sound["logit_gap"]["value"] <= limit
    assert ctl["logit_gap"]["value"] > limit
    picked = correct.sample(rec.reqs, seed, 10000)
    assert ctl["logit_gap"]["value"] == \
        correct.control_gaps(rec.dims, seed, picked).max()


def test_control_run_is_not_correct(tmp_path):
    out = tiny.run(tmp_path, "tiny-reason", 24, control=True)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > 0.08
    assert out["checks"]["window_compiles"]["value"] == 0


def test_a_compile_inside_the_window(tmp_path, monkeypatch):
    """A shape the warm-up never ran makes the run not correct."""
    monkeypatch.setattr(serve, "warm", lambda st, plan: None)
    root = tiny.root(tmp_path)
    mix = json.loads((root / "traffic" / "chat.json").read_text())
    mix["arrival"]["preroll_s"] = 0.0         # the window's first step
    (root / "traffic" / "unwarmed.json").write_text(json.dumps(mix))
    cell = dict(tiny.CELLS["tiny-chat"], traffic="unwarmed")
    cell["engine"] = dict(cell["engine"], max_batch=3, token_budget=99)
    (root / "cells" / "tiny-unwarmed.json").write_text(json.dumps(cell))
    out = runner.run_cell(spec.load_cell("tiny-unwarmed", root), 25, 2.0,
                          trace_on=False, t_process=0.0, bench=tiny.BENCH,
                          devs=jax.devices(), log=lambda s: None)
    assert not out["correct"]
    assert out["checks"]["window_compiles"]["value"] > 0
