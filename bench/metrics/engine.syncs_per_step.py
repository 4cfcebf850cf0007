"""Blocking device-to-host reads (``engine.sync`` spans) per
``sched.step`` span that dispatched device work, in the traced window
(model step)."""

from bench.harness import spans


def read(rec):
    w = spans.of(rec)
    steps = w.dispatched_steps() if w is not None else []
    if not steps:
        return None
    return sum(len(w.inside(s, spans.SYNC)) for s in steps) / len(steps)
