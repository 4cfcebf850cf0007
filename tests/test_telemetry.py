"""Serving telemetry suite: registry accuracy, span completeness, export
schemas, determinism, snapshot round-trip, and the disabled fast path.

The quantile tests pin the log-bucketed streaming histogram against
numpy's exact percentiles (the ~2% GAMMA error bound, with slack).  The
lifecycle tests drive a real scheduler run with tracing on and assert
every submitted request emits exactly one terminal ``finish`` event
whose reason matches the scheduler's own ``Track.finish_reason`` —
including rejected and deadline-missed requests, which never reach the
decode loop.  The export tests validate the Chrome ``trace_event`` /
Prometheus / JSON-lines schemas structurally, so a field rename cannot
silently break Perfetto or a scrape config.  Determinism compares the
*event-name sequences* of two identical runs (timestamps legitimately
differ).  The snapshot test round-trips a mid-flight engine+scheduler
through ``serving/snapshot.py`` and requires the restored registry and
tracer to carry the full pre-snapshot history.
"""

import json
import urllib.request

import numpy as np
import pytest

from repro.serving.telemetry import (GAMMA, Clock, Histogram,
                                     MetricsRegistry, Telemetry,
                                     _escape, _unescape,
                                     start_metrics_server,
                                     stop_metrics_server)
from repro.serving.trace import FINISH, PHASES, Tracer

PAGE = 8


# ---------------------------------------------------------------- histogram


@pytest.mark.parametrize("dist", ["uniform", "lognormal", "exponential"])
def test_histogram_quantiles_match_numpy(dist):
    rng = np.random.default_rng(0)
    xs = {"uniform": rng.uniform(1e-4, 10.0, 5000),
          "lognormal": rng.lognormal(0.0, 2.0, 5000),
          "exponential": rng.exponential(0.05, 5000)}[dist]
    h = Histogram()
    for v in xs:
        h.observe(float(v))
    for q in (0.05, 0.5, 0.9, 0.95, 0.99):
        exact = float(np.percentile(xs, 100 * q))
        got = h.quantile(q)
        assert got == pytest.approx(exact, rel=0.05), (dist, q)
    assert h.count == len(xs)
    assert h.sum == pytest.approx(float(xs.sum()), rel=1e-9)
    assert h.mean == pytest.approx(float(xs.mean()), rel=1e-9)


def test_histogram_edge_cases():
    h = Histogram()
    assert h.quantile(0.5) == 0.0          # empty
    h.observe(0.0)
    h.observe(-1.0)                        # zero bucket absorbs <= 0
    h.observe(5.0)
    assert h.zero == 2
    assert h.quantile(0.0) == 0.0          # clamped to observed min
    assert h.quantile(1.0) == 5.0          # clamped to observed max
    # single positive value: every quantile collapses onto it
    h1 = Histogram()
    h1.observe(3.0)
    assert h1.quantile(0.5) == 3.0
    # round-trip preserves quantiles exactly (same buckets)
    h2 = Histogram()
    h2.load_state(json.loads(json.dumps(h.state())))
    assert h2.quantile(0.95) == h.quantile(0.95)
    assert (h2.count, h2.sum, h2.zero) == (h.count, h.sum, h.zero)


@pytest.mark.parametrize("dist", ["uniform", "lognormal", "exponential"])
def test_merged_histogram_quantiles_match_numpy(dist):
    # per-host aggregation: two hosts observe disjoint halves of one
    # stream; the merged histogram's quantiles must match numpy on the
    # concatenation within the same ~2% GAMMA bound as a single
    # histogram (log-bucket merge is exact — shared boundaries)
    rng = np.random.default_rng(1)
    xs = {"uniform": rng.uniform(1e-4, 10.0, 6000),
          "lognormal": rng.lognormal(0.0, 2.0, 6000),
          "exponential": rng.exponential(0.05, 6000)}[dist]
    a, b = Histogram(), Histogram()
    for v in xs[:2000]:
        a.observe(float(v))
    for v in xs[2000:]:
        b.observe(float(v))
    a.merge(b)
    for q in (0.05, 0.5, 0.9, 0.95, 0.99):
        exact = float(np.percentile(xs, 100 * q))
        assert a.quantile(q) == pytest.approx(exact, rel=0.05), (dist, q)
    assert a.count == len(xs)
    assert a.sum == pytest.approx(float(xs.sum()), rel=1e-9)
    # merging an empty histogram is the identity
    snap = (a.count, a.sum, a.zero, dict(a.buckets))
    a.merge(Histogram())
    assert (a.count, a.sum, a.zero, dict(a.buckets)) == snap


def test_histogram_relative_error_bound():
    # the design bound: representative = geometric bucket midpoint, so
    # any single sample is recovered within sqrt(GAMMA)-1
    bound = GAMMA ** 0.5 - 1
    for v in (0.001, 0.37, 1.0, 42.0, 9999.0):
        h = Histogram()
        for _ in range(10):
            h.observe(v)
        assert abs(h.quantile(0.5) - v) / v <= bound + 1e-12


# ----------------------------------------------------------------- registry


def test_registry_kinds_labels_and_state():
    reg = MetricsRegistry()
    c = reg.counter("req_total", codec="bdi")
    c.inc()
    c.inc(-1)                              # reversal deltas are legal
    c.inc(5)
    assert reg.counter("req_total", codec="bdi") is c
    assert reg.counter("req_total", codec="raw") is not c
    reg.gauge("depth").set(7)
    reg.histogram("lat_seconds", codec="bdi").observe(0.25)
    with pytest.raises(ValueError):
        reg.gauge("req_total")             # name pinned to one kind
    assert {lbl["codec"] for lbl, _ in reg.series("req_total")} \
        == {"bdi", "raw"}

    reg2 = MetricsRegistry()
    reg2.load_state(json.loads(json.dumps(reg.state())))
    assert reg2.snapshot() == reg.snapshot()
    assert reg2.counter("req_total", codec="bdi").value == 5


def test_registry_merge_aggregates_hosts():
    # two per-host registries fold into one: counters/gauges add,
    # histograms merge bucket-wise, label sets union
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("req_total", codec="bdi").inc(3)
    b.counter("req_total", codec="bdi").inc(4)
    b.counter("req_total", codec="raw").inc(1)      # only on host b
    a.gauge("depth").set(2)
    b.gauge("depth").set(5)
    for v in (0.1, 0.2):
        a.histogram("lat_seconds").observe(v)
    b.histogram("lat_seconds").observe(0.4)
    a.merge(b)
    assert a.counter("req_total", codec="bdi").value == 7
    assert a.counter("req_total", codec="raw").value == 1
    assert a.gauge("depth").value == 7               # sum semantics
    h = a.histogram("lat_seconds")
    assert h.count == 3 and h.sum == pytest.approx(0.7)
    assert (h.min, h.max) == (0.1, 0.4)
    # merging b again is additive, and b itself is untouched
    assert b.counter("req_total", codec="bdi").value == 4
    # kind conflicts refuse to merge rather than corrupt
    c = MetricsRegistry()
    c.gauge("req_total").set(1)
    with pytest.raises(ValueError):
        c.merge(a)


def test_label_escape_round_trip():
    for s in ('plain', 'a"b', 'back\\slash', 'multi\nline',
              '\\n is not a newline', 'tricky\\"\\n\\\\end', ''):
        assert _unescape(_escape(s)) == s, repr(s)
    # exposition output parses back to the original label value
    reg = MetricsRegistry()
    reg.counter("esc_total", tag='a"b\\c\nd\\ne').inc()
    line = [ln for ln in reg.to_prometheus().splitlines()
            if ln.startswith("esc_total{")][0]
    quoted = line[line.index('="') + 2:line.rindex('"}')]
    assert _unescape(quoted) == 'a"b\\c\nd\\ne'


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("req_total", help="requests", codec="bdi").inc(3)
    reg.gauge("pool_used").set(11)
    h = reg.histogram("lat_seconds", codec="bdi")
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    text = reg.to_prometheus()
    assert "# HELP req_total requests" in text
    assert "# TYPE req_total counter" in text
    assert '\nreq_total{codec="bdi"} 3\n' in text
    assert "# TYPE pool_used gauge" in text
    assert "\npool_used 11\n" in text
    assert "# TYPE lat_seconds summary" in text
    assert '\nlat_seconds{codec="bdi",quantile="0.5"} ' in text
    assert '\nlat_seconds_count{codec="bdi"} 3\n' in text
    assert '\nlat_seconds_sum{codec="bdi"} ' in text
    # label values escape quotes/backslashes/newlines
    reg.counter("esc_total", tag='a"b\\c\nd').inc()
    assert r'esc_total{tag="a\"b\\c\nd"} 1' in reg.to_prometheus()


def test_jsonl_line_is_valid_json():
    reg = MetricsRegistry()
    reg.histogram("lat_seconds").observe(0.5)
    rec = json.loads(reg.to_jsonl_line(iteration=3, final=True))
    assert rec["iteration"] == 3 and rec["final"] is True
    assert "ts" in rec
    assert rec["metrics"]["lat_seconds"]["type"] == "histogram"
    (s,) = rec["metrics"]["lat_seconds"]["series"]
    assert s["count"] == 1 and "p95" in s


def test_metrics_http_server():
    reg = MetricsRegistry()
    reg.counter("x_total", codec="bdi").inc(3)
    server = start_metrics_server([reg], port=0)
    try:
        port = server.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            body = r.read().decode()
            assert r.headers["Content-Type"].startswith("text/plain")
        assert 'x_total{codec="bdi"} 3' in body
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/health", timeout=10) as r:
            assert r.read().decode() == "ok\n"
    finally:
        server.shutdown()


def test_stop_metrics_server_joins_thread_and_closes_socket():
    reg = MetricsRegistry()
    reg.counter("x_total").inc()
    server = start_metrics_server([reg], port=0)
    port = server.server_address[1]
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=10) as r:
        assert r.read().decode() == "ok\n"
    stop_metrics_server(server)
    t = server._serve_thread
    assert not t.is_alive()                 # thread joined, not leaked
    with pytest.raises(OSError):            # listening socket closed
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/health", timeout=2)
    stop_metrics_server(server)             # idempotent


# ------------------------------------------------------------------- tracer


def test_disabled_tracer_records_nothing():
    tr = Tracer(Clock(), enabled=False)
    tr.event(1, "submit")
    tr.phase(1, "queued")
    tr.iteration(0, decode_tokens=3)
    tr.finish(1, "length")
    assert not tr.events and not tr.slices and not tr.counters
    assert not tr._open
    # a disabled tracer still exports a valid (empty) trace
    t = tr.to_chrome_trace()
    assert [e["ph"] for e in t["traceEvents"]] == ["M", "M"]


def test_tracer_span_slices_only_when_enabled():
    on, off = Tracer(Clock(), enabled=True), Tracer(Clock(), enabled=False)
    for tr in (on, off):
        with tr.span("sched.step", iteration=3) as sp:
            sp.set_metadata(decode_rows=2)
            with tr.span("engine.sync"):
                pass
    assert not off.spans
    assert [s[2] for s in on.spans] == ["engine.sync", "sched.step"]
    (i0, i1, _, iargs), (o0, o1, _, oargs) = on.spans
    assert o0 <= i0 <= i1 <= o1
    assert iargs is None and oargs == {"iteration": 3, "decode_rows": 2}
    xs = [e for e in on.to_chrome_trace()["traceEvents"] if e["ph"] == "X"]
    assert [(e["tid"], e["cat"], e["name"]) for e in xs] == \
        [(0, "span", "engine.sync"), (0, "span", "sched.step")]
    again = Tracer(Clock())
    again.load_state(json.loads(json.dumps(on.state())))
    assert again.spans == on.spans


def test_tracer_phases_and_finish():
    tr = Tracer(Clock(), enabled=True)
    tr.event(7, "submit")
    tr.phase(7, "queued")
    tr.phase(7, "prefill")
    tr.phase(7, "decode")
    tr.finish(7, "eos")
    assert [ph for *_, ph in tr.slices] == ["queued", "prefill", "decode"]
    assert all(ph in PHASES for *_, ph in tr.slices)
    assert not tr._open                       # finish closed the span
    assert tr.finish_reasons() == {7: ["eos"]}
    assert tr.event_names(7) == [(7, "submit"), (7, FINISH)]


# ------------------------------------------------- scheduler-driven tracing


@pytest.fixture(scope="module")
def small_model():
    import jax

    from repro.configs.registry import get_arch
    from repro.models.api import get_model

    cfg = get_arch("yi-6b").reduced(n_layers=2, d_model=64)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, params


def _workload():
    # normal finishes, a chunk-split long prompt, and a guaranteed
    # deadline miss (30-token prompt cannot prefill inside 1 iteration
    # at budget 24)
    return [
        (0, [1 + j for j in range(12)], {"max_new_tokens": 4}),
        (1, [5, 6, 7], {"max_new_tokens": 6}),
        (2, [9] * 30, {"max_new_tokens": 3}),
        (3, [2] * 30, {"max_new_tokens": 50, "deadline": 1}),
    ]


def _traced_run(cfg, params):
    from repro.serving.engine import PagedKVEngine
    from repro.serving.scheduler import ContinuousScheduler

    tel = Telemetry(trace=True)
    eng = PagedKVEngine(cfg, params, page_size=PAGE, n_pool_pages=96,
                        max_batch=3, telemetry=tel)
    sched = ContinuousScheduler(eng, token_budget=24, telemetry=tel)
    for rid, prompt, kw in _workload():
        sched.submit(rid, prompt, **kw)
    sched.run()
    return sched, tel


@pytest.fixture(scope="module")
def traced_run(small_model):
    cfg, params = small_model
    return _traced_run(cfg, params)


def test_span_lifecycle_completeness(traced_run):
    sched, tel = traced_run
    fin = sched.finished()
    assert fin, "run finished nothing"
    reasons = tel.tracer.finish_reasons()
    # every request: exactly one terminal event, matching the Track
    for rid, tr in fin.items():
        assert reasons.get(rid) == [str(tr.finish_reason)], rid
    assert set(reasons) == set(fin)
    assert "deadline" in {r for rs in reasons.values() for r in rs}
    # lifecycle instants present for requests that produced tokens
    names = tel.tracer.event_names()
    for rid, tr in fin.items():
        assert (rid, "submit") in names
        if tr.out_tokens:
            assert (rid, "first_token") in names
    # no request left with an open phase slice
    assert not tel.tracer._open


def test_rejected_requests_get_terminal_events(small_model):
    from repro.serving.engine import PagedKVEngine
    from repro.serving.scheduler import ContinuousScheduler

    cfg, params = small_model
    tel = Telemetry(trace=True)
    eng = PagedKVEngine(cfg, params, page_size=PAGE, n_pool_pages=96,
                        max_batch=2, telemetry=tel)
    sched = ContinuousScheduler(eng, token_budget=24, max_queue=1,
                                telemetry=tel)
    for rid in range(4):
        sched.submit(rid, [1 + rid] * 6, max_new_tokens=2)
    sched.run()
    reasons = tel.tracer.finish_reasons()
    fin = sched.finished()
    assert set(reasons) == set(fin) == {0, 1, 2, 3}
    assert all(len(rs) == 1 for rs in reasons.values())
    assert "rejected" in {rs[0] for rs in reasons.values()}
    assert sched.stats["rejected"] >= 1


def test_chrome_trace_schema(traced_run):
    _, tel = traced_run
    trace = json.loads(json.dumps(tel.tracer.to_chrome_trace()))
    assert trace["displayTimeUnit"] == "ms"
    evs = trace["traceEvents"]
    assert {e["ph"] for e in evs} == {"M", "X", "i", "C"}
    for e in evs:
        assert {"ph", "pid", "tid", "name"} <= set(e)
        if e["ph"] == "X" and e["cat"] == "span":
            assert e["tid"] == 0
            assert e["name"].startswith(("sched.", "engine.",
                                         "prefix_cache."))
            assert e["dur"] >= 0 and e["ts"] >= 0
        elif e["ph"] == "X":
            assert e["cat"] == "request"
            assert e["name"] in PHASES
            assert e["dur"] >= 0 and e["ts"] >= 0
        elif e["ph"] == "i":
            assert e["s"] in ("t", "p")
            assert isinstance(e["args"], dict)
        elif e["ph"] == "C":
            assert "iteration" in e["args"]
    # the iteration timeline carries the token-budget split and pool
    # occupancy the thesis's latency argument is about
    counter_names = {e["name"] for e in evs if e["ph"] == "C"}
    assert {"decode_tokens", "prefill_tokens", "token_budget",
            "pool_used_pages", "free_list_depth",
            "dispatch_ms"} <= counter_names
    # and the host phases of each iteration, on the scheduler track
    span_names = {e["name"] for e in evs if e.get("cat") == "span"}
    assert {"sched.step", "sched.admit", "sched.retire",
            "engine.begin_cohort", "engine.prepare", "engine.dispatch",
            "engine.sync", "engine.publish",
            "engine.cohort_advance"} <= span_names


def test_two_seeded_runs_trace_identically(small_model):
    cfg, params = small_model
    s1, t1 = _traced_run(cfg, params)
    s2, t2 = _traced_run(cfg, params)
    assert t1.tracer.event_names() == t2.tracer.event_names()
    assert t1.tracer.finish_reasons() == t2.tracer.finish_reasons()
    assert [(r, ph) for _, _, r, ph in t1.tracer.slices] \
        == [(r, ph) for _, _, r, ph in t2.tracer.slices]
    assert {r: tr.out_tokens for r, tr in s1.finished().items()} \
        == {r: tr.out_tokens for r, tr in s2.finished().items()}


def test_telemetry_snapshot_restore_roundtrip(small_model, tmp_path):
    from repro.serving.engine import PagedKVEngine
    from repro.serving.scheduler import ContinuousScheduler
    from repro.serving.snapshot import restore_snapshot, save_snapshot

    cfg, params = small_model
    tel = Telemetry(trace=True)
    eng = PagedKVEngine(cfg, params, page_size=PAGE, n_pool_pages=96,
                        max_batch=3, telemetry=tel)
    sched = ContinuousScheduler(eng, token_budget=24, telemetry=tel)
    sched.submit(0, [1 + j for j in range(12)], max_new_tokens=8)
    sched.submit(1, [3] * 5, max_new_tokens=6)
    for _ in range(4):                       # mid-flight snapshot point
        sched.step()
    save_snapshot(str(tmp_path), eng, sched, step=1)
    eng2, sched2 = restore_snapshot(str(tmp_path), cfg, params)

    # one shared Telemetry on the restored pair, full history intact
    assert sched2.telemetry is eng2.telemetry
    tel2 = sched2.telemetry
    assert tel2.registry.snapshot() == tel.registry.snapshot()
    assert tel2.tracer.enabled
    assert tel2.tracer.events == tel.tracer.events
    assert tel2.tracer.slices == tel.tracer.slices
    assert tel2.tracer.spans == tel.tracer.spans
    assert sched2.stats == sched.stats
    assert eng2.stats == eng.stats

    # the restored run keeps recording into the same series
    sched2.run()
    reasons = tel2.tracer.finish_reasons()
    assert set(reasons) == {0, 1}
    assert all(len(rs) == 1 for rs in reasons.values())
    h = tel2.registry.histogram("serve_ttft_seconds",
                                codec=eng2.codec.name)
    assert h.count == 2


# ------------------------------------------- host spans on the profiler


def _profiled_host_spans(trace_dir):
    """(name, start_ns, end_ns, stats) of the serving loop's host spans
    in the newest profiler trace under ``trace_dir``."""
    import glob
    import os

    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("sched.", "engine.",
                                      "prefix_cache.")):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(spans, parent, name):
    return [s for s in spans if s[0] == name and s is not parent
            and parent[1] <= s[1] and s[2] <= parent[2]]


def test_step_spans_nest_on_the_profiler_clock(small_model, tmp_path):
    import jax

    from repro.serving.engine import PagedKVEngine
    from repro.serving.prefix_cache import PrefixCache
    from repro.serving.scheduler import ContinuousScheduler

    cfg, params = small_model
    eng = PagedKVEngine(cfg, params, page_size=PAGE, n_pool_pages=96,
                        max_batch=2,
                        prefix_cache=PrefixCache.for_model(cfg, PAGE))
    sched = ContinuousScheduler(eng, token_budget=24)
    head = [1 + j for j in range(2 * PAGE)]
    sched.submit(0, head + [50], max_new_tokens=PAGE + 2)
    events = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        for it in range(PAGE + 2):
            if it == 3:      # its whole stored prompt is in the cache
                sched.submit(1, head + [60], max_new_tokens=2)
            events.append(sched.step())
    finally:
        jax.profiler.stop_trace()
    spans = _profiled_host_spans(str(tmp_path))
    steps = {s[3]["iteration"]: s for s in spans if s[0] == "sched.step"}
    assert sorted(steps) == [ev["iteration"] for ev in events]
    filled = [ev["iteration"] for ev in events
              if ev["decoded"] and 0 in ev["decoded"]][PAGE - 1]
    for ev in events:
        st = steps[ev["iteration"]]
        assert len(_inside(spans, st, "engine.dispatch")) == 1
        if not ev["decoded"]:
            continue
        assert st[3]["decode_rows"] == len(ev["decoded"])
        pubs = _inside(spans, st, "engine.publish")
        for p in pubs:
            assert len(_inside(spans, p, "engine.sync")) == 1
        # the token read follows the dispatch; a publish adds its own
        # read, a prefix hit's admission its checksum read
        dispatch = _inside(spans, st, "engine.dispatch")[0]
        syncs = _inside(spans, st, "engine.sync")
        token = [s for s in syncs if s[1] >= dispatch[2]][0]
        assert not any(p[1] <= token[1] <= p[2] for p in pubs)
        verifies = _inside(spans, st, "engine.verify")
        assert len(syncs) == 1 + len(pubs) + len(verifies)
        assert bool(pubs) == (ev["iteration"] == filled)
    admit = _inside(spans, steps[3], "sched.admit")
    assert events[3]["admitted"] == [1] and len(admit) == 1
    for name in ("engine.begin_cohort", "prefix_cache.lookup",
                 "engine.verify", "engine.sync"):
        assert len(_inside(spans, admit[0], name)) == 1, name
    verify = _inside(spans, admit[0], "engine.verify")[0]
    assert len(_inside(spans, verify, "engine.sync")) == 1
