"""Model operations per token, for the whole step's share of the peak.

A token costs two operations per matrix parameter it passes (the LM head
only where logits are made: decode, not prefill) and ``4 * n * H * D``
per layer for attention over its ``n`` visible positions.
"""


def layer_params(dims: dict) -> int:
    d, h, kv, dh, f = (dims["d_model"], dims["n_heads"], dims["n_kv_heads"],
                       dims["head_dim"], dims["d_ff"])
    return d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f


def decode_flops(ctx: int, dims: dict) -> float:
    """One decode token whose context (itself included) is ``ctx``."""
    lp = layer_params(dims)
    return (2.0 * (dims["n_layers"] * lp + dims["d_model"] * dims["vocab"])
            + dims["n_layers"] * 4.0 * ctx * dims["n_heads"]
            * dims["head_dim"])


def prefill_flops(n: int, pos_sum: int, dims: dict) -> float:
    """``n`` prompt tokens whose 0-based positions sum to ``pos_sum``."""
    lp = layer_params(dims)
    return (2.0 * n * dims["n_layers"] * lp
            + dims["n_layers"] * 4.0 * (pos_sum + n) * dims["n_heads"]
            * dims["head_dim"])
