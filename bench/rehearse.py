"""Compile a cell's worst programs for a described TPU v5e, without a chip,
and print what the compiler says they need.

  JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell>

The programs are the decode step at the widest page table the traffic
reaches and the mixed step (decode plus a prefill chunk) at the largest
cohort rows and scratch length of the cell's shape plan.  For each it
prints ``memory_analysis()`` in bytes, beside the weights, the page pool
and the chip's capacity: the numbers ``max_batch``, ``prefill_chunk`` and
``n_pool_pages`` are chosen from.  The parameter shapes are the
architecture module's (``bench/arch/<model_type>.py``); the page pool, the
decode tails and the cohort scratch are the dense engine's, KV heads by
head size per token.  A configuration whose pages hold something else,
such as a latent row, is sized by a rehearsal of its own.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="the engine's max_batch (default: the cell's)")
    ap.add_argument("--rows", type=int, default=None,
                    help="cohort rows of the mixed step (default: the "
                         "plan's largest)")
    ap.add_argument("--tmax", type=int, default=None,
                    help="scratch length of the mixed step (default: the "
                         "plan's largest)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro.kernels._backend as backend
    from repro import codecs
    from repro.serving import engine as E

    from bench.harness import serve, spec

    backend.default_interpret = lambda: False     # compile the kernels
    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.load_cell(args.workload)
    if args.max_batch:
        cell.cell["engine"]["max_batch"] = args.max_batch
    plan = serve.shape_plan(cell)
    cfg = cell.arch.arch_config(cell.cell["config"], cell.dims)
    e = cell.cell["engine"]
    codec = codecs.resolve(cell.cell["codec"])
    dev = topologies.get_topology_desc(
        platform="tpu", topology_name=args.topology).devices[0]
    one = SingleDeviceSharding(dev)

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    params = sds(jax.eval_shape(lambda: cell.arch.model(cell.dims, 0)))
    pools = sds(jax.eval_shape(lambda: codec.init_pools(
        e["n_pool_pages"], cfg.n_kv_heads, e["page_size"], cfg.head_dim)))
    lyr, b = cfg.n_layers, e["max_batch"]
    tail = jax.ShapeDtypeStruct((lyr, b, cfg.n_kv_heads, e["page_size"],
                                 cfg.head_dim), jnp.float32, sharding=one)
    pt = jax.ShapeDtypeStruct((lyr, b, plan["pmax"]), jnp.int32,
                              sharding=one)
    vec = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one)
    act = jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one)
    dec = (pt, vec, vec, vec, vec, act)
    kw = dict(cfg=cfg, codec=codec, use_fused=codec.has_fused_kernels)
    nbytes = lambda t: sum(x.size * x.dtype.itemsize  # noqa: E731
                           for x in jax.tree.leaves(t))
    report = {"cell": cell.name, "plan": plan,
              "weights_bytes": nbytes(params), "pool_bytes": nbytes(pools),
              "programs": {}}

    def show(name, compiled):
        m = compiled.memory_analysis()
        report["programs"][name] = {
            k: getattr(m, k) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "alias_size_in_bytes", "temp_size_in_bytes")}
        print(name, json.dumps(report["programs"][name]), flush=True)

    show("decode_step", E._decode_step.lower(
        params, pools, tail, tail, *dec, **kw).compile())
    rows = args.rows or plan["rows"][-1]
    tmax = args.tmax or plan["tmax"][-1]
    scr = jax.ShapeDtypeStruct((lyr, rows, tmax, cfg.n_kv_heads,
                                cfg.head_dim), jnp.float32, sharding=one)
    can = jax.ShapeDtypeStruct(
        (lyr, rows, 0 if codec.lossless else tmax, cfg.n_kv_heads,
         cfg.head_dim), jnp.float32, sharding=one)
    ptoks = jax.ShapeDtypeStruct((rows, e["prefill_chunk"]), jnp.int32,
                                 sharding=one)
    offs = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one)
    show(f"mixed_step rows={rows} tmax={tmax}", E._mixed_step.lower(
        params, pools, tail, tail, scr, scr, can, can, *dec, ptoks, offs,
        page=e["page_size"], **kw).compile())
    report["scratch_bytes"] = 2 * nbytes(scr) + 2 * nbytes(can)
    report["chip_bytes"] = 15.75 * 2 ** 30
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
