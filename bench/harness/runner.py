"""One run of one cell: set-up, the measured window, the metrics, and the
comparison that decides ``correct``."""

from __future__ import annotations

import gc
import json
import shutil
import sys
import time
from types import SimpleNamespace

import jax

from . import correct, serve, spec, stats, trace
from .peaks import peaks
from .spec import BENCH, Cell

ROOT = BENCH.parent
TRACE_DIR = BENCH / ".trace"
OK_REASONS = ("length", "eos")


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def metrics_for(bench: dict, cell: str, kind: str) -> list[dict]:
    """The cell's ``end_to_end`` or ``per_layer`` entries of the
    benchmark file: those that list it, or list no cells at all."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str):
    """The per-layer metric's own reader, ``bench/metrics/<name>.py``."""
    return spec.load_module(BENCH / "metrics" / f"{name}.py",
                            "bench_metric").read


def device_info(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest device (0 where not reported)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def end_to_end(rec) -> dict:
    """The end-to-end numbers, from the harness's own timestamps."""
    return {"ttft_p50_ms": stats.percentile(
                stats.ttft(rec.reqs, rec.seconds), 50) * 1e3,
            "tbt_p95_ms": stats.percentile(
                stats.gaps(rec.reqs, rec.seconds), 95) * 1e3,
            "output_tok_s": stats.tokens_in(rec.reqs, rec.seconds)
            / rec.seconds,
            "setup_s": rec.setup_s}


def _counters(st) -> dict:
    c = dict(st.eng.stats)
    if st.eng.prefix_cache is not None:
        c.update({f"prefix_{k}": v
                  for k, v in st.eng.prefix_cache.stats.items()})
    return c


def serve_window(cell: Cell, seed: int, seconds: float, *, trace_on: bool,
                 t_process: float, devs, log=print):
    """Set up, warm up, pre-roll and drive the window.  Returns the run
    record; the program's device state is freed before it returns."""
    watch = serve.compile_watch()
    h0, m0 = watch.cache["hits"], watch.cache["misses"]
    st = serve.build(cell, seed)
    serve.warm(st, serve.shape_plan(cell))
    if trace_on:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    d = serve.preroll(st, cell, seed, seconds)
    setup_s = d.clock() - t_process
    log(f"setup_s: {setup_s}; persistent cache hits "
        f"{watch.cache['hits'] - h0}, misses {watch.cache['misses'] - m0}")
    c0, n0 = _counters(st), watch.n
    waiting_open = len(st.sched.waiting)
    with jax.profiler.TraceAnnotation(trace.SLICE):
        d.run_until(d.t_open + seconds)
    if trace_on:
        jax.profiler.stop_trace()
    c1 = _counters(st)
    waiting_close = len(st.sched.waiting)
    n = watch.n - n0
    log(f"compiles in window: {n} {sorted(set(watch.names[len(watch.names) - n:])) if n else ''}")
    reqs = serve.relative(d)
    for rid, reason in d.retired.items():
        d.reqs[rid].reason = reason
    rec = SimpleNamespace(
        reqs=reqs, steps=d.steps, seconds=seconds, setup_s=setup_s,
        arch=cell.arch, dims=cell.dims, page=st.eng.page, cell=cell,
        counters={k: c1[k] - c0.get(k, 0) for k in c1},
        compiles_in_window=n, memory_peak_bytes=memory_peak(devs),
        waiting=(waiting_open, waiting_close),
        trace=None, peaks=None)
    del st, d
    gc.collect()
    return rec


def run_cell(cell: Cell, seed: int, seconds: float, *, trace_on: bool,
             t_process: float, bench: dict, devs, log=print,
             control: bool = False) -> dict:
    """Run the cell once; return the result line's object, ``checks``
    (each number compared, with its limit) as its last key.  With
    ``control`` the float8 reference's choices stand in for the served
    tokens, so ``correct`` has to come out false."""
    rec = serve_window(cell, seed, seconds, trace_on=trace_on,
                       t_process=t_process, devs=devs, log=log)
    out = {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
           "device": dict(device_info(devs),
                          memory_peak_bytes=rec.memory_peak_bytes)}
    if trace_on:
        rec.peaks = peaks(out["device"]["kind"])
        rec.trace = trace.reduce(trace.events(str(TRACE_DIR)),
                                 spec.kernel_ops(cell.root))
        if rec.trace is not None:
            out["device"]["busy_s"] = rec.trace["busy_s"]
            out["device"]["window_s"] = rec.trace["window_s"]
            out["breakdown"] = {"device_ops": rec.trace["device_ops"],
                                "idle_gaps": rec.trace["idle_gaps"]}
        for m in metrics_for(bench, cell.name, "per_layer"):
            v = reader(m["name"])(rec)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = end_to_end(rec)
        for m in metrics_for(bench, cell.name, "end_to_end"):
            out["metrics"][m["name"]] = {"value": values[m["name"]],
                                         "unit": m["unit"]}
    out["attempted"] = sum(1 for r in rec.reqs if 0.0 <= r.due < seconds)
    bad = [r for r in rec.reqs
           if getattr(r, "reason", OK_REASONS[0]) not in OK_REASONS]
    out["failed"] = len(bad)
    out["checks"] = check(cell, seed, rec, len(bad), log, control)
    out["correct"] = all(c["value"] <= c["limit"]
                         for c in out["checks"].values())
    return out


def check(cell: Cell, seed: int, rec, n_bad: int, log=print,
          control: bool = False) -> dict:
    """Compare a seeded sample of what the window served with the plain
    reference, and count the programs compiled inside the window."""
    chk = cell.cell["check"]
    picked = correct.sample(rec.reqs, seed, chk["sample_tokens"])
    t0 = time.perf_counter()
    gaps = correct.control_gaps if control else correct.served_gaps
    gap = (float(gaps(rec.arch, rec.dims, seed, picked).max())
           if picked else float("inf"))
    log(f"reference over {len(picked)} requests, "
        f"{sum(len(r.out) for r in picked)} served tokens, "
        f"{time.perf_counter() - t0:.1f} s")
    checks = {"logit_gap": {"value": gap, "limit": chk["logit_gap_limit"]},
              "failed_requests": {"value": n_bad, "limit": 0},
              "window_compiles": {"value": rec.compiles_in_window,
                                  "limit": 0}}
    for k, c in checks.items():
        print(f"check {k}: {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    return checks
