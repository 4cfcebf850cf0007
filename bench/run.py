"""Run one benchmark cell on the accelerator this process finds.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--control 1`` the float8 reference's choices stand in for the
served tokens in the comparison, so ``correct`` has to come out false.

The cell is ``bench/cells/<cell>.json``; it names its model configuration
(``bench/configs/``) and traffic mix (``bench/traffic/``).  The metrics it
reports are those ``BENCHMARK.json`` lists for it: the end-to-end ones
with ``--trace 0``, the per-layer ones (read by ``bench/metrics/<name>.py``
from a profiler trace of the window) with ``--trace 1``.  The last line
of standard output is one JSON object; the last lines of standard error
give each number compared with the plain reference beside its limit.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def compile_cache(jax) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says); every program
    is kept, so only a cell's first run in a checkout compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import runner, spec
    cell = spec.load_cell(args.workload)

    import jax
    devs = jax.devices()
    want = cell.cell["chips"]
    if devs[0].platform != "tpu" or len(devs) < want:
        print(f"bench: needs {want} TPU chip(s); JAX found "
              f"{runner.device_info(devs)}", file=sys.stderr)
        return 2
    devs = devs[:want]
    compile_cache(jax)
    out = runner.run_cell(cell, args.seed, args.seconds,
                          trace_on=bool(args.trace), t_process=T_PROCESS,
                          bench=runner.benchmark(), devs=devs,
                          log=lambda s: print(s, flush=True),
                          control=bool(args.control))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
