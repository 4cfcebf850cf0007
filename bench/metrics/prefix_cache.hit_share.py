"""Share of looked-up prompt tokens served from prefix-cache pages over
the window: ``hit_tokens / lookup_tokens`` deltas (prefix cache)."""


def read(rec):
    look = rec.counters.get("prefix_lookup_tokens", 0)
    return 100.0 * rec.counters["prefix_hit_tokens"] / look if look else None
