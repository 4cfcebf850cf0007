"""Median device idle time inside a ``sched.step`` span that dispatched
device work, over such steps in the traced window: the host time of the
scheduler's iteration that the device waits out (scheduler).  A median,
so that one rare stall in the window does not move it; the span report
(``python3 -m bench.harness.spans``) counts the stalls."""

import statistics

from bench.harness import spans


def read(rec):
    w = spans.of(rec)
    idles = w.step_idles() if w is not None else []
    return 1e-6 * statistics.median(idles) if idles else None
