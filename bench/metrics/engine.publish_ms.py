"""Median host time of one ``engine.publish`` span in the traced window:
gathering the filled pages, compressing them and reading back their
sizes and checksums (its ``engine.sync``), then the page and
prefix-cache bookkeeping (KV pool and codec).  A median, so that one
rare stall in the window does not move it."""

import statistics

from bench.harness import spans


def read(rec):
    w = spans.of(rec)
    if w is None:
        return None
    d = [b - a for _, a, b in w.named("engine.publish")]
    return 1e-6 * statistics.median(d) if d else None
