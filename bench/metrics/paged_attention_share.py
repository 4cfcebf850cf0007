"""The fused decode-attention kernel's device time over the device's busy
time in the traced window (kernels)."""


def read(rec):
    if rec.trace is None or rec.trace["busy_s"] <= 0:
        return None
    ks = rec.trace["kernel_s"]["paged_attention"]
    return 100.0 * ks / rec.trace["busy_s"] if ks > 0 else None
