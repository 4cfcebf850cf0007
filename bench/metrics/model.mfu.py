"""Whole step's share of the chip's bf16 peak: the operations of every
token processed in the window, as the architecture module counts them
(``decode_flops``, ``prefill_flops``), over the window and the peak of
``device_kind`` (model step)."""


def read(rec):
    if rec.peaks is None:
        return None
    flops = 0.0
    for s in rec.steps:
        if 0.0 <= s.t0 and s.t1 <= rec.seconds:
            flops += sum(rec.arch.decode_flops(c, rec.dims)
                         for c in s.decode_ctx)
            flops += rec.arch.prefill_flops(s.prefill_n, s.prefill_pos_sum,
                                            rec.dims)
    return 100.0 * flops / (rec.seconds * rec.peaks["bf16_flops"]) \
        if flops else None
