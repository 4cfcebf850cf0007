"""Mean number of sequences decoded per dispatched step in the window
(scheduler)."""


def read(rec):
    n = [len(s.decode_ctx) for s in rec.steps
         if 0.0 <= s.t0 and s.t1 <= rec.seconds and s.decode_ctx]
    return sum(n) / len(n) if n else None
