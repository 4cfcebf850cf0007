"""The fused decode-attention kernel's share of its roofline in the traced
window: the least time its calls could take, counted from each row's
valid tokens (``bench/roofline/paged_attention.py``), over the kernel's
device time in the trace (kernels)."""

from bench.roofline import paged_attention


def read(rec):
    if rec.trace is None or rec.peaks is None:
        return None
    ks = rec.trace["kernel_s"]["paged_attention"]
    if ks <= 0:
        return None
    bound = sum(rec.dims["n_layers"]
                * paged_attention.bound_s(s.decode_ctx, rec.dims, rec.page,
                                          rec.peaks)
                for s in rec.steps
                if 0.0 <= s.t0 and s.t1 <= rec.seconds and s.decode_ctx)
    return 100.0 * bound / ks
