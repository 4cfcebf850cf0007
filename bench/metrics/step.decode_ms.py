"""Mean device time of one decode-only step, ``jit(_decode_step)``, in the
traced window (model step)."""


def read(rec):
    if rec.trace is None:
        return None
    d = [x for n, ds in rec.trace["modules"].items() if "_decode_step" in n
         for x in ds]
    return 1e3 * sum(d) / len(d) if d else None
