"""The program's host spans beside the device's busy time, from the
profiler trace of the window.

The serving loop annotates its host phases (``sched.step`` and the
``sched.*``, ``engine.*`` and ``prefix_cache.*`` spans inside it) in
the profiler's trace, beside the device's events.  ``rows`` reads the
newest ``.xplane.pb`` under a directory into the rows
``bench.harness.trace`` works on, ``[plane, line, name, start_ns,
dur_ns]``: the device planes' op and module lines, and the host spans of
the program and of the harness (``bench.*``).  ``Window`` keeps what
falls inside the traced slice (``bench.traced``), each span's name cut
at any ``#`` metadata suffix, so the per-layer readers and the idle
split work on rows only and can be checked on a small recorded set.

    PYTHONPATH=src python3 -m bench.harness.spans [trace_dir]

prints, for the trace a ``--trace 1`` run left, the window's device idle
time split by the innermost host span open at each idle instant, the
steps whose idle is a stall (over ``STALL`` times the median), and how
each decoding step's program lines up with its host spans: the token
read's end after the program's end, the program's start after its
dispatch's start, and the bounds these put on an offset between the
device's clock and the host's.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import statistics
import sys

from . import trace

PREFIXES = ("sched.", "engine.", "prefix_cache.", "bench.")
STEP, DISPATCH, SYNC = "sched.step", "engine.dispatch", "engine.sync"
DECODE_MODULES = ("jit__decode_step", "jit__mixed_step")
STALL = 10          # a step idle this many times the median is a stall


def _newest(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def rows(path: str) -> list[list]:
    """Device op and module rows and the named host spans of one
    ``.xplane.pb``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (trace.OPS, trace.MODULES):
                continue
            for e in line.events:
                name = e.name
                if line.name == trace.OPS:
                    name = trace.op_name(name)
                elif not (device or name.startswith(PREFIXES)):
                    continue
                out.append([plane.name, line.name, name,
                            float(e.start_ns), float(e.duration_ns)])
    return out


class Window:
    """The traced slice: the host spans inside it (``(name, start,
    end)``, the name cut at any ``#`` metadata suffix), the device's
    busy intervals (every device's ops merged) and its module events,
    the device's times moved by ``offset_ns`` (see ``clock_bounds``)."""

    def __init__(self, rows: list[list], offset_ns: float = 0.0):
        sl = [r for r in rows if r[2] == trace.SLICE]
        self.lo = min(r[3] for r in sl)
        self.hi = max(r[3] + r[4] for r in sl)
        # device times less ``offset_ns``: the host's time of each event
        # where the device's timestamps read ``offset_ns`` late
        dev = [r[:3] + [r[3] - offset_ns, r[4]] for r in rows
               if r[0].startswith("/device:")]
        busy = trace._merge([trace._clip(r[3], r[3] + r[4], self.lo,
                                         self.hi)
                             for r in dev if r[1] == trace.OPS])
        self._a = [a for a, b in busy if b > a]
        self._b = [b for a, b in busy if b > a]
        self.modules = sorted((r[2], r[3], r[3] + r[4]) for r in dev
                              if r[1] == trace.MODULES)
        self.spans = sorted(((r[2].split("#", 1)[0], r[3], r[3] + r[4])
                             for r in rows
                             if not r[0].startswith("/device:")
                             and r[2] != trace.SLICE
                             and self.lo <= r[3]
                             and r[3] + r[4] <= self.hi),
                            key=lambda s: (s[1], -s[2]))
        self._starts = [s[1] for s in self.spans]

    def busy(self, a: float, b: float) -> float:
        """Device busy time inside [a, b], in ns."""
        t = 0.0
        i = bisect.bisect_right(self._b, a)
        while i < len(self._a) and self._a[i] < b:
            t += min(self._b[i], b) - max(self._a[i], a)
            i += 1
        return t

    def idle(self, a: float, b: float) -> float:
        """Device idle time inside [a, b], in ns."""
        return (b - a) - self.busy(a, b)

    def named(self, name: str) -> list[tuple]:
        return [s for s in self.spans if s[0] == name]

    def inside(self, parent: tuple, name: str) -> list[tuple]:
        """Spans called ``name`` that open and close inside ``parent``."""
        _, a, b = parent
        i = bisect.bisect_left(self._starts, a)
        out = []
        while i < len(self.spans) and self.spans[i][1] < b:
            s = self.spans[i]
            if s[0] == name and s[2] <= b and s is not parent:
                out.append(s)
            i += 1
        return out

    def dispatched_steps(self) -> list[tuple]:
        """``sched.step`` spans that dispatched device work."""
        return [s for s in self.named(STEP) if self.inside(s, DISPATCH)]

    def step_idles(self) -> list[float]:
        """Device idle time (ns) inside each step that dispatched."""
        return [self.idle(a, b) for _, a, b in self.dispatched_steps()]

    def idle_split(self) -> dict[str, float]:
        """Device idle time of the window (ns) by the innermost host
        span open at each idle instant; ``none`` where none is open."""
        pts = sorted({self.lo, self.hi}
                     | {x for _, a, b in self.spans for x in (a, b)})
        out: dict[str, float] = {}
        stack: list[tuple] = []
        j = 0
        for a, b in zip(pts, pts[1:]):
            while j < len(self.spans) and self.spans[j][1] <= a:
                stack.append(self.spans[j])
                j += 1
            stack = [s for s in stack if s[2] > a]
            idle = self.idle(a, b)
            if idle > 0:
                k = stack[-1][0] if stack else "none"
                out[k] = out.get(k, 0.0) + idle
        return out

    def decode_programs(self) -> list[tuple]:
        """For each dispatched step that ran a decode program: its
        ``engine.dispatch`` span, its token read (the first
        ``engine.sync`` after the dispatch) and the decode module event
        that overlaps the dispatch-to-read interval the most.  Overlap,
        not order, matches them, so a module timed before its dispatch
        (a clock offset longer than the dispatch's latency) is still
        found; a decode program lasts far longer than any offset."""
        out = []
        # one device runs one program at a time: ends follow starts
        mods = sorted((m for m in self.modules
                       if m[0].startswith(DECODE_MODULES)),
                      key=lambda m: m[1])
        ends = [m[2] for m in mods]
        for st in self.dispatched_steps():
            d = self.inside(st, DISPATCH)[0]
            syncs = [s for s in self.inside(st, SYNC) if s[1] >= d[2]]
            if not syncs:
                continue
            a, b = d[1], syncs[0][2]
            i = bisect.bisect_right(ends, a)
            best, most = None, 0.0
            while i < len(mods) and mods[i][1] < b:
                over = min(mods[i][2], b) - max(mods[i][1], a)
                if over > most:
                    best, most = mods[i], over
                i += 1
            if best is not None:
                out.append((d, syncs[0], best))
        return out

    def token_sync_lags(self) -> list[float]:
        """The token read's end less its program's end, per decoding
        step, in ns."""
        return [s[2] - m[2] for _, s, m in self.decode_programs()]

    def clock_bounds(self) -> tuple[float, float] | None:
        """Bounds (ns) on the offset of the device's timestamps from the
        host's (device time less host time of one instant), from
        causality alone: a program starts after its dispatch starts and
        ends before its token read ends.  An upper edge below 0 says the
        device's times read early by at least that much.  Inside the
        bounds the trace cannot tell an offset from a read that lags its
        program, nor a program that starts late after its dispatch."""
        progs = self.decode_programs()
        if not progs:
            return None
        return (max(m[2] - s[2] for _, s, m in progs),
                min(m[1] - d[1] for d, _, m in progs))


_LAST: list = [None, None]      # (path, mtime), Window | None


def of(rec) -> Window | None:
    """The window of the run's trace (read once per run), or None where
    the run was not traced or its trace holds no traced slice."""
    if rec.trace is None:
        return None
    from .runner import TRACE_DIR
    path = _newest(str(TRACE_DIR))
    key = (path, os.path.getmtime(path))
    if _LAST[0] != key:
        r = rows(path)
        _LAST[:] = [key, Window(r) if any(x[2] == trace.SLICE for x in r)
                    else None]
    return _LAST[1]


def stalls(idles: list[float]) -> list[float]:
    """The step idles over ``STALL`` times their median."""
    if not idles:
        return []
    med = statistics.median(idles)
    return [x for x in idles if x > STALL * med]


def _ms3(v: list[float]) -> list[float] | None:
    """Least, median and largest of ``v`` (ns) in ms."""
    v = sorted(v)
    return [v[0] * 1e-6, statistics.median(v) * 1e-6, v[-1] * 1e-6] \
        if v else None


def report(w: Window) -> dict:
    """The idle split (ms and share), what named child spans cover of the
    idle time inside ``sched.step``, the steps' idle and its stalls, the
    token-read lags, the programs' starts after their dispatches, the
    clock-offset bounds these give, and each decoding step's round trip
    (dispatch start to token-read end less the program's device time),
    which no offset moves."""
    split = w.idle_split()
    idle = sum(split.values())
    in_step = sum(v for k, v in split.items()
                  if not k.startswith(("bench.", "none")))
    idles = w.step_idles()
    stall = stalls(idles)
    progs = w.decode_programs()
    lags = [s[2] - m[2] for _, s, m in progs]
    bounds = w.clock_bounds()
    return {
        "window_ms": (w.hi - w.lo) * 1e-6, "idle_ms": idle * 1e-6,
        "split_ms": {k: v * 1e-6 for k, v in
                     sorted(split.items(), key=lambda kv: -kv[1])},
        "split_share": {k: v / idle for k, v in split.items()} if idle
        else {},
        "step_idle_child_share": 1 - split.get(STEP, 0.0) / in_step
        if in_step else None,
        "steps": len(w.named(STEP)), "dispatched": len(idles),
        "step_idle_ms_min_median_max": _ms3(idles),
        "step_idle_mean_ms": 1e-6 * sum(idles) / len(idles) if idles
        else None,
        "stall_steps": len(stall), "stall_idle_ms": 1e-6 * sum(stall),
        "token_syncs": len(lags),
        "lag_within_1ms_share": sum(0 <= x <= 1e6 for x in lags) / len(lags)
        if lags else None,
        "lag_ms_min_median_max": _ms3(lags),
        "start_after_dispatch_ms_min_median_max":
            _ms3([m[1] - d[1] for d, _, m in progs]),
        "clock_offset_bounds_ms": [x * 1e-6 for x in bounds] if bounds
        else None,
        "round_trip_ms_min_median_max":
            _ms3([(s[2] - d[1]) - (m[2] - m[1]) for d, s, m in progs]),
    }


def bracket(rows: list[list]) -> dict:
    """``report`` of the trace as recorded, with the idle split redone
    at each edge of the clock-offset bounds: where the device's
    timestamps are offset, the split lies between the two."""
    w = Window(rows)
    out = report(w)
    b = w.clock_bounds()
    out["split_ms_at_offset_bounds"] = [
        {k: v * 1e-6 for k, v in Window(rows, x).idle_split().items()}
        for x in b] if b else None
    return out


if __name__ == "__main__":
    from .runner import TRACE_DIR
    d = sys.argv[1] if len(sys.argv) > 1 else str(TRACE_DIR)
    print(json.dumps(bracket(rows(_newest(d)))))
