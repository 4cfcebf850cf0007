"""Qwen2: the dense decoder of ``llama.py``, with the QKV bias its
configurations carry (``attention_bias``)."""

from bench.arch.llama import (  # noqa: F401
    arch_config, decode_flops, dims_of, logits_at, model, one_layer,
    outer_params, prefill_flops)
