"""Reduce a profiler trace to device time, kernel time and idle gaps.

``events`` reads the ``.xplane.pb`` the JAX profiler writes into plain
rows ``[plane, line, name, start_ns, dur_ns]``: the device planes' op and
module lines, and the harness's own host spans (``bench.*``).
``reduce`` works on those rows only, so it can be checked on a small
recorded trace.  The traced slice is the host span ``bench.traced``.
"""

from __future__ import annotations

import glob
import os

OPS, MODULES = "XLA Ops", "XLA Modules"
SLICE = "bench.traced"
LOOPS = ("while", "conditional")      # ops that contain other ops


def op_name(text: str) -> str:
    """An op's own name out of its HLO text (``%fusion.3 = f32[...] ...``
    -> ``fusion.3``): the text also names the ops it reads."""
    return text.split(" = ", 1)[0].lstrip("%")


def events(trace_dir: str) -> list[list]:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    rows = []
    for plane in ProfileData.from_file(max(paths, key=os.path.getmtime)
                                       ).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in (OPS, MODULES):
                continue
            for e in line.events:
                if device or e.name.startswith("bench."):
                    name = op_name(e.name) if line.name == OPS else e.name
                    rows.append([plane.name, line.name, name,
                                 float(e.start_ns), float(e.duration_ns)])
    return rows


def _merge(iv: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def reduce(rows: list[list], kernels: dict[str, str]) -> dict | None:
    """Device busy time, per-op totals, per-module durations, each named
    kernel's time and the idle gaps of the traced slice.  A loop op counts
    toward busy time but not among the ops: its body's ops are rows too.

    ``kernels`` maps a kernel's metric name to a substring of its op name.
    Returns None when the trace holds no slice or no device op in it.
    """
    spans = [r for r in rows if r[2] == SLICE]
    if not spans:
        return None
    lo = min(r[3] for r in spans)
    hi = max(r[3] + r[4] for r in spans)
    ops = [r for r in rows if r[1] == OPS and r[3] < hi and r[3] + r[4] > lo]
    if not ops:
        return None
    devices = sorted({r[0] for r in ops})
    busy, gaps = 0.0, []
    for dev in devices:
        iv = _merge([_clip(r[3], r[3] + r[4], lo, hi)
                     for r in ops if r[0] == dev])
        busy += sum(b - a for a, b in iv)
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    per_op: dict[str, float] = {}
    for r in ops:
        if r[2].split(".")[0] in LOOPS:      # its body's ops are rows too
            continue
        a, b = _clip(r[3], r[3] + r[4], lo, hi)
        per_op[r[2]] = per_op.get(r[2], 0.0) + (b - a)
    kernel_ns = {k: sum(v for n, v in per_op.items() if sub in n)
                 for k, sub in kernels.items()}
    modules: dict[str, list[float]] = {}
    for r in rows:
        if r[1] == MODULES and lo <= r[3] and r[3] + r[4] <= hi:
            modules.setdefault(r[2], []).append(r[4])
    host = [r for r in rows if r[2].startswith("bench.") and r[2] != SLICE]

    def doing(a, b):
        mid = (a + b) / 2
        inside = [r for r in host if r[3] <= mid <= r[3] + r[4]]
        return min(inside, key=lambda r: r[4])[2] if inside else "other"

    gaps.sort(key=lambda g: g[0] - g[1])
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": busy * 1e-9 / len(devices),
            "devices": len(devices),
            "device_ops": [[n, v * 1e-9] for n, v in
                           sorted(per_op.items(), key=lambda kv: -kv[1])
                           [:10]],
            "idle_gaps": [[doing(a, b), (b - a) * 1e-9] for a, b in gaps[:10]],
            "kernel_s": {k: v * 1e-9 for k, v in kernel_ns.items()},
            "modules": {n: [d * 1e-9 for d in ds]
                        for n, ds in modules.items()}}
