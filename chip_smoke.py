"""Smoke run of the compressed-KV serving path on one TPU chip, at yi-6b's
published widths (d_model 4096, 32 query / 4 KV heads, head_dim 128,
d_ff 11008, vocab 64000, 32 layers) with random weights from seed 0.

Both phases go through the serving entry point a user calls,
``repro.launch.serve.generate``: PagedKVEngine + ContinuousScheduler +
prefix cache + host memory tier.

  A. codec ``bdi``, the fused Pallas decode-attention and page-fill
     kernels: 8 requests, 512-token prompts sharing a 384-token prefix,
     32 generated tokens each.
  B. codec ``adaptive``: every page is compressed by all five member
     codecs (``bdi`` and ``gbdi`` through their kernels) and attention
     runs on the jnp path: 4 requests, 256-token prompts sharing a
     192-token prefix, 16 generated tokens each.  Cut to 29 layers: the
     five member pools (2.3 GB at 32 layers) beside 12.1 GB of weights
     overflow the chip's 15.75 GiB, and 29 is the deepest cut whose
     programs leave about as much room as phase A's.

Each phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}``.  A failed check raises, so the script
exits non-zero and prints no last line; it does the same when JAX finds
no TPU.  Its timings are those of one cold run, not a benchmark.

Usage: python3 chip_smoke.py
"""

from __future__ import annotations

import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

from repro.configs.registry import get_arch  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    device_info, enable_compile_cache, generate)

ARCH = "yi-6b"
PHASES = (
    {"phase": "A", "codec": "bdi", "batch": 8, "prompt_len": 512,
     "shared_prefix": 384, "gen": 32},
    {"phase": "B", "codec": "adaptive", "batch": 4, "prompt_len": 256,
     "shared_prefix": 192, "gen": 16, "n_layers": 29},
)
# what may stay on the device between phases: the previous phase's
# weights (12.1 GB) and pools must be gone before the next one starts
_CARRYOVER_LIMIT = 1 << 30

_COMPILE = {"s": 0.0, "n": 0}


def _on_duration(event: str, secs: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE["s"] += secs
        _COMPILE["n"] += 1


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def run_phase(p: dict) -> dict:
    gc.collect()
    carried = sum(a.nbytes for a in jax.live_arrays())
    check(carried < _CARRYOVER_LIMIT,
          f"{carried} bytes still live on the device before phase "
          f"{p['phase']}")
    c_s, c_n = _COMPILE["s"], _COMPILE["n"]
    t0 = time.perf_counter()
    out = generate(ARCH, smoke=False, scheduler=True, prefix_cache=True,
                   tier_host_mb=256, codec=p["codec"], batch=p["batch"],
                   prompt_len=p["prompt_len"],
                   shared_prefix=p["shared_prefix"], gen=p["gen"],
                   n_layers=p.get("n_layers"))
    wall = time.perf_counter() - t0
    cfg = get_arch(ARCH)
    layers = p.get("n_layers", cfg.n_layers)
    reasons = [r["reason"] for r in out["per_request"].values()]
    toks = out["tokens"]
    hit_rate = out["prefix_cache"]["hit_rate"]
    line = {
        **p, "device": out["device"], "n_layers": out["n_layers"],
        "depth_cut": (f"{cfg.n_layers} -> {layers} layers"
                      if layers != cfg.n_layers else None),
        "widths": {"d_model": cfg.d_model, "n_heads": cfg.n_heads,
                   "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
                   "d_ff": cfg.d_ff, "vocab": cfg.vocab},
        "use_fused": out["use_fused"],
        "use_fused_fill": out["use_fused_fill"],
        "finish_reasons": reasons,
        "tokens_generated": sum(len(t) for t in toks),
        "kv_compression_ratio": out["kv_compression_ratio"],
        "prefix_cache_hit_rate": hit_rate,
        "tier": out["tier"],
        "live_bytes_at_start": carried,
        "peak_bytes_in_use_since_start": jax.devices()[0].memory_stats()[
            "peak_bytes_in_use"],
        "smoke_timing_not_a_benchmark": {
            "wall_s": wall, "compile_s": _COMPILE["s"] - c_s,
            "compiles": _COMPILE["n"] - c_n},
    }
    print(json.dumps(line), flush=True)

    check(out["device"]["platform"] == "tpu", "ran on a TPU")
    check(out["n_layers"] == layers, f"{layers} layers ran")
    check(out["use_fused_fill"], "page fill ran through the kernels")
    if p["codec"] == "bdi":
        check(out["use_fused"], "bdi attention ran through the kernel")
        check(out["kv_compression_ratio"] > 1, "bdi compresses the KV")
    check(reasons == ["length"] * p["batch"],
          f"every request finished 'length': {reasons}")
    check(all(len(t) == p["gen"] and all(0 <= x < cfg.vocab for x in t)
              for t in toks), "every token id is in [0, vocab)")
    check(hit_rate > 0, "the prefix cache served shared-prefix pages")
    return line


def main() -> None:
    dev = device_info()
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {dev}")
    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    for p in PHASES:
        run_phase(p)
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
