"""Mean host time of one ``sched.admit`` span that started a cohort
(holds an ``engine.begin_cohort``) in the traced window: queue pop,
prefix-cache lookup, checksum check of the hit pages and the cohort's
set-up (scheduler)."""

from bench.harness import spans


def read(rec):
    w = spans.of(rec)
    if w is None:
        return None
    d = [s[2] - s[1] for s in w.named("sched.admit")
         if w.inside(s, "engine.begin_cohort")]
    return 1e-6 * sum(d) / len(d) if d else None
