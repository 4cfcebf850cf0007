"""Operations and bytes of one call of the fused decode-attention kernel
(``paged_attention_tail``, one layer, one decode step), counted from the
valid tokens of each active row, not from the padded page-table width.

A row whose context holds ``c`` tokens (the new one included) reads
``(c - 1) // page`` whole compressed pages and ``(c - 1) % page + 1``
tail tokens.  A compressed token row costs ``D`` int8 bytes plus an f32
base and scale, per KV head, for K and for V; a tail token costs ``D``
f32 values for K and for V.  q is read and the output written once, f32.
The operations are the two matrix products, ``4 * c * H * D``; the
elementwise dequantisation is not counted.
"""

KERNEL_OP = "_paged_attention_tail"   # the kernel's op name in a trace


def call_cost(ctx: list[int], dims: dict, page: int) -> tuple[float, float]:
    """(flops, bytes) of one call over rows of context lengths ``ctx``."""
    h, kv, d = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    flops = nbytes = 0.0
    for c in ctx:
        paged = (c - 1) // page * page
        tail = c - paged
        flops += 4.0 * c * h * d
        nbytes += (2 * paged * kv * (d + 8) + 2 * tail * kv * d * 4
                   + 2 * h * d * 4)
    return flops, nbytes


def bound_s(ctx: list[int], dims: dict, page: int, pk: dict) -> float:
    """The least time the chip could take for the call."""
    flops, nbytes = call_cost(ctx, dims, page)
    return max(flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"])
