"""Median queue wait over requests due in the window: from the due time
to the end of the step that admitted the request (scheduler).  One not
admitted by the window's end enters at ``seconds - due``."""

from bench.harness.stats import percentile


def read(rec):
    waits = []
    for r in rec.reqs:
        if 0.0 <= r.due < rec.seconds:
            at = r.admit_t if r.admit_t is not None else rec.seconds
            waits.append(min(at, rec.seconds) - r.due)
    return percentile(waits, 50) * 1e3 if waits else None
