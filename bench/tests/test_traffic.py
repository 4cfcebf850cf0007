"""The traffic generator: deterministic per seed, within its clips, the
same schedule for every seed, and every mix a data file."""

import json
import pathlib

import numpy as np
import pytest

from bench.harness import traffic

BENCH = pathlib.Path(__file__).resolve().parents[1]
CHAT = json.loads((BENCH / "traffic" / "chat-shared-prefix.json").read_text())
REASON = json.loads((BENCH / "traffic" / "reason-long-decode.json")
                    .read_text())


def _mix(mix, **arrival):
    return dict(mix, arrival=dict(mix["arrival"], **arrival))


def _key(reqs):
    return [(r.prompt, r.max_new, r.due, r.session) for r in reqs]


@pytest.mark.parametrize("seed", [0, 7, 3_000_000_000])
def test_open_loop_is_deterministic_per_seed(seed):
    mix = _mix(CHAT, rate_per_s=0.6)
    a = traffic.Traffic(mix, seed, 64000, 51.0).initial
    b = traffic.Traffic(mix, seed, 64000, 51.0).initial
    assert _key(a) == _key(b)
    assert _key(a) != _key(traffic.Traffic(mix, seed + 1, 64000,
                                           51.0).initial)


def test_open_loop_same_schedule_every_seed():
    mix = _mix(CHAT, rate_per_s=0.6)
    a = traffic.Traffic(mix, 1, 64000, 51.0).initial
    b = traffic.Traffic(mix, 2, 64000, 51.0).initial
    assert [(len(r.prompt), r.max_new, r.due) for r in a] == \
        [(len(r.prompt), r.max_new, r.due) for r in b]
    assert a[0].prompt != b[0].prompt


def test_open_loop_clips_medians_and_sharing():
    mix = _mix(CHAT, rate_per_s=2.0, preroll_s=0.0)
    reqs = traffic.Traffic(mix, 5, 64000, 200.0).initial
    assert len(reqs) == 400
    assert all(0.0 <= r.due < 200.0 for r in reqs)
    assert all(np.diff([r.due for r in reqs]) > 0)
    suf = [len(r.prompt) - 512 for r in reqs]
    out = [r.max_new for r in reqs]
    assert min(suf) >= 16 and max(suf) <= 384
    assert min(out) >= 32 and max(out) <= 512
    assert abs(np.median(suf) - 96) <= 2 and abs(np.median(out) - 128) <= 2
    heads = {}
    for r in reqs:
        assert all(1 <= t < 64000 for t in r.prompt)
        heads.setdefault(tuple(r.prompt[:512]), 0)
        heads[tuple(r.prompt[:512])] += 1
    counts = sorted(heads.values(), reverse=True)
    assert counts == list(traffic.zipf_counts(400, 8, 1.0))
    assert counts[0] > 2.5 * counts[3] > 0


def test_zipf_counts_sum_and_order():
    c = traffic.zipf_counts(37, 8, 1.0)
    assert c.sum() == 37 and all(np.diff(c) <= 0)


def test_closed_loop_resumes_sessions_and_is_deterministic():
    mix = _mix(REASON, clients=16)
    a = traffic.Traffic(mix, 11, 152064, 51.0)
    b = traffic.Traffic(mix, 11, 152064, 51.0)
    assert _key(a.initial) == _key(b.initial)
    c = traffic.Traffic(mix, 12, 152064, 51.0)
    assert a.initial[0].prompt != c.initial[0].prompt
    for r in a.initial:
        assert 1 <= r.max_new <= 4096
        assert 128 <= len(r.prompt) <= 1024 + 4096
    # ages spread over the output: resumed prompts carry 0..most of it
    carried = sorted(len(r.prompt) for r in a.initial)
    assert carried[-1] - carried[0] > 1000
    nxt = []
    for r in a.initial[:1] * 40:
        n, delay = a.follow(r)
        assert n.client == r.client and delay == 0.0
        nxt.append(n)
    assert all(128 <= len(r.prompt) <= 1024 and 1024 <= r.max_new <= 4096
               for r in nxt)
    assert len({r.rid for r in a.initial + nxt}) == 56


def test_lognormal_quantiles_median_and_clip():
    x = traffic.lognormal_quantiles(
        1001, {"median": 2048, "sigma": 0.5, "min": 1024, "max": 4096})
    assert np.median(x) == 2048 and x.min() == 1024 and x.max() == 4096


def test_shape_ranges():
    assert traffic.max_context(CHAT) == 512 + 384 + 512
    assert traffic.fresh_prompt_range(CHAT) == (528, 896)
    assert traffic.max_context(REASON) == 1024 + 4096
    assert traffic.fresh_prompt_range(REASON) == (128, 1024)
    assert traffic.max_hit_tokens(CHAT) == 512
    assert traffic.max_hit_tokens(REASON) == 0


# mixes the benchmark does not hold yet, written as data alone
SESSIONS = {
    "arrival": {"kind": "poisson", "rate_per_s": 1.0, "preroll_s": 0.0,
                "phases": [[1.0, 1.0]]},
    "classes": [{"share": 1.0,
                 "prefix": {"groups": 0, "tokens": 0, "zipf_s": 1.0},
                 "prompt_tokens": {"median": 40, "sigma": 0.5, "min": 8,
                                   "max": 100},
                 "output_tokens": {"median": 20, "sigma": 0.5, "min": 4,
                                   "max": 50},
                 "turns": 3, "think_s": 2.5}]}
MIXED = {
    "arrival": {"kind": "poisson", "rate_per_s": 2.0, "preroll_s": 0.0,
                "phases": [[10.0, 3.0], [30.0, 1.0 / 3.0]]},
    "classes": [
        {"share": 3.0, "prefix": {"groups": 4, "tokens": 64, "zipf_s": 1.0},
         "prompt_tokens": {"median": 30, "sigma": 0.4, "min": 8, "max": 64},
         "output_tokens": {"median": 30, "sigma": 0.4, "min": 8, "max": 64},
         "turns": 1, "think_s": 0.0},
        {"share": 1.0, "prefix": {"groups": 0, "tokens": 0, "zipf_s": 1.0},
         "prompt_tokens": {"median": 900, "sigma": 0.3, "min": 500,
                           "max": 2000},
         "output_tokens": {"median": 600, "sigma": 0.3, "min": 300,
                           "max": 1200},
         "turns": 1, "think_s": 0.0}]}


def test_multi_turn_sessions_carry_the_conversation():
    tr = traffic.Traffic(SESSIONS, 3, 1000, 60.0)
    r = tr.initial[0]
    r.out = list(range(5, 5 + r.max_new))
    n2, delay = tr.follow(r)
    assert delay == 2.5 and n2.session == r.session and n2.turn == 1
    assert n2.prompt[:len(r.prompt) + len(r.out)] == r.prompt + r.out
    n2.out = [7] * n2.max_new
    n3, _ = tr.follow(n2)
    assert n3.turn == 2 and n3.prompt[:len(n2.prompt)] == n2.prompt
    n3.out = [1]
    assert tr.follow(n3) is None
    assert traffic.max_context(SESSIONS) == 3 * (100 + 50)
    assert traffic.max_hit_tokens(SESSIONS) == 2 * 100 + 50
    assert traffic.fresh_prompt_range(SESSIONS) == (8, 100 + 2 * 150)


def test_classes_share_the_queue_and_bursts_keep_the_mean_rate():
    tr = traffic.Traffic(MIXED, 9, 1000, 400.0)
    reqs = tr.initial
    assert len(reqs) == 800
    long = [r for r in reqs if r.max_new >= 300]
    assert len(long) == 200
    assert all(500 <= len(r.prompt) <= 2000 for r in long)
    due = np.array([r.due for r in reqs])
    assert due.max() < 400.0 * 1.05
    per_phase = np.histogram(due % 40.0, bins=[0, 10, 40])[0]
    # 10 s at 3x and 30 s at a third: three quarters arrive in the burst
    assert per_phase[0] / len(reqs) == pytest.approx(0.75, abs=0.05)


def test_warp_is_the_identity_when_steady():
    t = np.array([0.0, 0.5, 3.25, 10.0])
    assert traffic.warp(t, [[1.0, 1.0]]) == pytest.approx(t)
