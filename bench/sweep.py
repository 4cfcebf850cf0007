"""Find an open-loop cell's knee: the highest arrival rate at which the
waiting queue does not grow across a window.

  python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 0.5,1,2

One process runs a window at each rate on a fresh engine (the compiled
programs are shared) and prints a JSON line per rate: completed tokens
per second, median TTFT, and the waiting queue at the window's opening and
close.  The mix file keeps the chosen rate as a number.
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import jax

    from bench.harness import runner, spec
    from bench.run import compile_cache
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"sweep: needs a TPU; JAX found {devs}", file=sys.stderr)
        return 2
    compile_cache(jax)
    for rate in (float(r) for r in args.rates.split(",")):
        cell = spec.load_cell(args.workload)
        cell.mix["arrival"]["rate_per_s"] = rate
        rec = runner.serve_window(cell, args.seed, args.seconds,
                                  trace_on=False, t_process=time.perf_counter(),
                                  devs=devs[:1],
                                  log=lambda s: print(s, file=sys.stderr))
        e = runner.end_to_end(rec)
        print(json.dumps({"rate": rate, "waiting_open": rec.waiting[0],
                          "waiting_close": rec.waiting[1],
                          "ttft_p50_ms": e["ttft_p50_ms"],
                          "tbt_p95_ms": e["tbt_p95_ms"],
                          "output_tok_s": e["output_tok_s"],
                          "attempted": sum(1 for r in rec.reqs
                                           if 0 <= r.due < args.seconds)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
