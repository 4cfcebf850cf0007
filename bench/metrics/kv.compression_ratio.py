"""Raw over compressed bytes of the KV pages published in the window,
from the sizes the device reported (KV pool and codec)."""


def read(rec):
    comp = rec.counters.get("bytes_compressed", 0)
    return rec.counters["bytes_raw"] / comp if comp > 0 else None
