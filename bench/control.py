"""Readings that set a cell's correctness limit, many seeds in one process.

  python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed it runs the cell's window as a benchmark run does, then
decides ``correct`` twice through the run's own check: once on the served
tokens (the program's reading) and once with the float8 reference's
choices in their place (the control's reading, which has to come out not
correct).  The limit lies between the largest program reading and the
smallest control reading.  One JSON line per seed.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from bench.harness import runner, spec
    from bench.run import compile_cache
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"control: needs a TPU; JAX found {devs}", file=sys.stderr)
        return 2
    compile_cache(jax)
    log = lambda s: print(s, file=sys.stderr)  # noqa: E731
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = spec.load_cell(args.workload)
        rec = runner.serve_window(cell, seed, args.seconds, trace_on=False,
                                  t_process=time.perf_counter(),
                                  devs=devs[:1], log=log)
        bad = sum(1 for r in rec.reqs if getattr(
            r, "reason", "length") not in runner.OK_REASONS)
        line = {"seed": seed,
                "output_tok_s": runner.end_to_end(rec)["output_tok_s"]}
        for side, ctl in (("program", False), ("control", True)):
            chk = runner.check(cell, seed, rec, bad, log, control=ctl)
            line[side] = {"correct": all(c["value"] <= c["limit"]
                                         for c in chk.values()),
                          "checks": chk}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
