"""Pages the decode-attention kernel walked, over the pages of the padded
``[max_batch, PMAX]`` tables a walk of every entry would visit, in the
window: the engine's ``attn_pages_read / attn_pages_padded`` counter
deltas, in % (kernels).  Reads nothing from a program without them."""


def read(rec):
    padded = rec.counters.get("attn_pages_padded", 0)
    if padded <= 0:
        return None
    return 100.0 * rec.counters.get("attn_pages_read", 0) / padded
