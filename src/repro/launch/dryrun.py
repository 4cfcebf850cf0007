import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST run before any other import (jax locks device count and platform
# on first init).  The dry-run compiles for 512 virtual host devices; on a
# machine with an accelerator it must still never claim the chip.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

NOTE: the XLA_FLAGS / JAX_PLATFORMS assignments above intentionally
precede every import — jax locks the device count on first initialization.

For train shapes this lowers a full train_step (fwd + bwd + AdamW update)
under the production sharding rules; for prefill shapes, model.prefill;
for decode shapes, a serve_step (one token against a seq_len KV cache).
``.lower().compile()`` succeeding proves the distribution config is
coherent; ``memory_analysis`` proves it fits; ``cost_analysis`` +
HLO-collective parsing feed the roofline (benchmarks/roofline.py).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-9b \
      --shape train_4k [--multi-pod] [--out out.json]

Each invocation runs one cell in a fresh process (the 40-cell matrix is
driven by benchmarks/bench_dryrun.py).
"""

import argparse
import functools
import json
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SHAPES, applicable_shapes
from repro.configs.registry import get_arch
from repro.distributed import axes as AX
from repro.distributed import sharding as SH
from repro.launch.mesh import make_production_mesh
from repro.models import frontends
from repro.models.api import get_model
from repro.optim import AdamWConfig, adamw_init, adamw_update

# archs too big for replicated f32 moments on 16GiB chips use bf16 moments
MOMENT_DTYPE = {"arctic-480b": "q8"}
# gradient-accumulation dtype: arctic's 480B f32 accumulator alone would be
# 7.5GiB/chip; bf16 accumulation halves it (quantization noise ~1e-3 of the
# grad scale, folded into the §Perf error analysis)
GRAD_ACC_DTYPE = {"arctic-480b": "bf16"}

# gradient-accumulation microbatches per arch for train_4k: bounds the
# per-layer remat checkpoints ([L, B_micro, S, D]) + attention transients
# to fit 16GiB HBM.  Derived from the XLA memory-usage reports (see
# EXPERIMENTS.md §Dry-run).
MICROBATCHES = {
    "arctic-480b": 16, "internvl2-76b": 16, "gemma3-27b": 8,
    "qwen2.5-14b": 4, "yi-9b": 4, "yi-6b": 4, "deepseek-v2-lite-16b": 2,
    "hymba-1.5b": 4, "seamless-m4t-large-v2": 2, "xlstm-350m": 1,
}


def _sds(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def build_train_step(model, ocfg, n_micro: int = 1,
                     acc_dtype=jnp.float32):
    """fwd+bwd (+optimizer) with gradient accumulation over microbatches.

    Each scan iteration runs a full forward/backward on 1/n_micro of the
    batch; activation checkpoints live only within one iteration, so peak
    temp memory scales with the microbatch, while gradients accumulate in
    a params-sized f32 buffer.
    """
    def split_micro(batch):
        def f(x):
            b = x.shape[0]
            assert b % n_micro == 0, (b, n_micro)
            return x.reshape((n_micro, b // n_micro) + x.shape[1:])
        return jax.tree.map(f, batch)

    def train_step(params, opt_state, batch):
        if n_micro == 1:
            loss, grads = jax.value_and_grad(model.loss)(params, batch)
        else:
            micro = split_micro(batch)

            def acc_body(carry, mb):
                loss_acc, gacc = carry
                l, g = jax.value_and_grad(model.loss)(params, mb)
                gacc = jax.tree.map(
                    lambda a, b: a + b.astype(acc_dtype), gacc, g)
                return (loss_acc + l, gacc), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dtype),
                              params)
            (loss, grads), _ = jax.lax.scan(acc_body, (jnp.float32(0), g0),
                                            micro)
            loss = loss / n_micro
            grads = jax.tree.map(lambda g: g / n_micro, grads)
        params, opt_state, metrics = adamw_update(params, grads, opt_state,
                                                  ocfg)
        metrics["loss"] = loss
        return params, opt_state, metrics
    return train_step


def lower_cell(arch_name: str, shape_name: str, multi_pod: bool,
               *, kv_compressed: bool = False, fsdp: bool = True,
               remat: bool = True, microbatches: int | None = None,
               sp: bool = False):
    """Returns (lowered, compiled, info dict)."""
    cfg = get_arch(arch_name)
    shape = SHAPES[shape_name]
    if shape_name not in applicable_shapes(cfg):
        raise SystemExit(f"SKIP: {shape_name} not applicable to {arch_name} "
                         "(full-attention arch; see DESIGN.md)")
    AX.set_sp(sp)
    model = get_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    ocfg = AdamWConfig(moment_dtype=MOMENT_DTYPE.get(arch_name, "f32"))
    n_micro = microbatches if microbatches is not None else \
        MICROBATCHES.get(arch_name, 1)

    t0 = time.time()
    with AX.use_mesh(mesh):
        params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        p_shard = SH.param_shardings(params_shape, mesh, fsdp=fsdp)

        if shape.kind == "train":
            opt_shape = jax.eval_shape(
                functools.partial(adamw_init, cfg=ocfg), params_shape)
            o_shard = SH.param_shardings(opt_shape, mesh, fsdp=fsdp)
            batch_shape = frontends.batch_struct(cfg, shape)
            b_specs = SH.batch_specs(batch_shape, mesh)
            b_shard = jax.tree.map(
                lambda s: jax.sharding.NamedSharding(mesh, s), b_specs,
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
            acc_dtype = (jnp.bfloat16 if GRAD_ACC_DTYPE.get(
                arch_name) == "bf16" else jnp.float32)
            step = jax.jit(
                build_train_step(model, ocfg, n_micro, acc_dtype),
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None),
                donate_argnums=(0, 1))
            lowered = step.lower(params_shape, opt_shape, batch_shape)

        elif shape.kind == "prefill":
            batch_shape = frontends.batch_struct(cfg, shape)
            b_shard = jax.tree.map(
                lambda s: jax.sharding.NamedSharding(mesh, s),
                SH.batch_specs(batch_shape, mesh),
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
            cache_kw = {}
            if cfg.is_encdec:
                cache_kw["enc_len"] = frontends.enc_len_for(cfg, shape)
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len,
                                         **cache_kw))
            c_shard = jax.tree.map(
                lambda s: jax.sharding.NamedSharding(mesh, s),
                SH.cache_specs(cache_shape, mesh),
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))

            def prefill_step(params, batch):
                return model.prefill(params, batch, shape.seq_len)

            step = jax.jit(prefill_step,
                           in_shardings=(p_shard, b_shard),
                           out_shardings=(None, c_shard))
            lowered = step.lower(params_shape, batch_shape)

        else:  # decode
            cache_kw = {}
            if cfg.is_encdec:
                cache_kw["enc_len"] = frontends.enc_len_for(cfg, shape)
            if kv_compressed:
                from repro.models import transformer as _T
                cache_shape = jax.eval_shape(
                    lambda: _T.init_quant_cache(cfg, shape.global_batch,
                                                shape.seq_len))
                model = model._replace(decode_step=functools.partial(
                    _T.decode_step_quant, cfg))
            else:
                cache_shape = jax.eval_shape(
                    lambda: model.init_cache(shape.global_batch,
                                             shape.seq_len, **cache_kw))
            c_shard = jax.tree.map(
                lambda s: jax.sharding.NamedSharding(mesh, s),
                SH.cache_specs(cache_shape, mesh),
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
            tok_struct = jax.ShapeDtypeStruct((shape.global_batch,),
                                              jnp.int32)
            tok_shard = jax.sharding.NamedSharding(
                mesh, SH.batch_specs(tok_struct, mesh))

            def serve_step(params, cache, token, t):
                return model.decode_step(params, cache, token, t)

            step = jax.jit(serve_step,
                           in_shardings=(p_shard, c_shard, tok_shard, None),
                           out_shardings=(None, c_shard),
                           donate_argnums=(1,))
            lowered = step.lower(
                params_shape, cache_shape,
                jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32))

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    info = {
        "arch": arch_name, "shape": shape_name,
        "microbatches": n_micro if shape.kind == "train" else 0,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(np.prod(list(mesh.shape.values()))),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "param_bytes_global": int(sum(
            np.prod(l.shape) * l.dtype.itemsize
            for l in jax.tree.leaves(params_shape))),
    }
    return lowered, compiled, info


_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def _shape_bytes(txt: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(txt):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\(?[a-z0-9]+\[)")
_HEADER_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_DIMS_RE = re.compile(r"^([a-z0-9]+)\[([0-9,]*)\]")


def _parse_hlo(hlo_text: str):
    """Shared HLO parse: computations, symbol shapes, execution multipliers.

    Multipliers: while bodies/conds execute trip-count times (bound parsed
    from the condition's compare constant); fusion/to_apply bodies inherit
    their caller's multiplier.
    """
    lines = hlo_text.splitlines()
    comps: dict[str, list[str]] = {}
    sym: dict[str, tuple[str, list[int]]] = {}   # name -> (dtype, dims)
    cur = None
    for line in lines:
        m = _HEADER_RE.match(line)
        if m and "->" in line:
            cur = m.group(1)
            comps[cur] = []
            continue
        if cur is not None:
            comps[cur].append(line)
        dm = _DEF_RE.match(line)
        if dm:
            head = line.split(" = ", 1)[1]
            shape_txt = head.split(" ", 1)[0] if " " in head else head
            sm = _DIMS_RE.match(shape_txt)
            if sm:
                dims = [int(d) for d in sm.group(2).split(",") if d]
                sym[dm.group(1)] = (sm.group(1), dims)
            else:
                sym[dm.group(1)] = ("tuple", [])

    while_re = re.compile(r"while\(.*?\).*condition=%?([\w.\-]+).*"
                          r"body=%?([\w.\-]+)")
    calls_re = re.compile(
        r"(?:calls|to_apply|condition|body|true_computation|"
        r"false_computation)=%?([\w.\-]+)")
    branch_re = re.compile(r"branch_computations=\{([^}]*)\}")
    trip: dict[str, int] = {}
    parent: dict[str, str] = {}
    fusion_parent: dict[str, str] = {}
    for cname, body_lines in comps.items():
        for ln in body_lines:
            wm = while_re.search(ln)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                parent[body] = cname
                parent[cond] = cname
                t = 1
                for cl in comps.get(cond, []):
                    mc = re.search(r"constant\((\d+)\)", cl)
                    if mc:
                        t = max(t, int(mc.group(1)))
                trip[body] = t
                trip[cond] = t
            else:
                for ref in calls_re.findall(ln):
                    fusion_parent.setdefault(ref, cname)
                bm = branch_re.search(ln)
                if bm:
                    for ref in re.findall(r"%([\w.\-]+)", bm.group(1)):
                        fusion_parent.setdefault(ref, cname)

    mult_cache: dict[str, int] = {}

    def multiplier(cname: str, depth: int = 0) -> int:
        if cname in mult_cache or depth > 20:
            return mult_cache.get(cname, 1)
        m = 1
        if cname in trip:
            m = trip[cname] * multiplier(parent.get(cname, ""), depth + 1)
        elif cname in fusion_parent:
            m = multiplier(fusion_parent[cname], depth + 1)
        mult_cache[cname] = m
        return m

    return comps, sym, multiplier


def _nbytes(dt: str, dims: list[int]) -> int:
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims:
        n *= d
    return n * _DTYPE_BYTES[dt]


_DOT_RE = re.compile(r"\bdot\(([^)]*)\)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_SKIP_OPS = re.compile(
    r"\b(parameter|constant|get-tuple-element|tuple|bitcast|after-all|"
    r"partition-id|iota)\(")


def hlo_cost(hlo_text: str) -> dict:
    """Per-device executed FLOPs and HBM-traffic bytes from optimized HLO,
    with while-loop trip multipliers (XLA's cost_analysis counts loop
    bodies once — useless for scan-over-layers programs).

    flops: 2 * prod(result dims) * prod(contracted lhs dims) per dot.
    bytes: per top-level instruction (fusion boundary = HBM traffic
    model): result + operand bytes; fusion-internal ops excluded.
    """
    comps, sym, multiplier = _parse_hlo(hlo_text)
    flops = 0.0
    bytes_ = 0.0
    fusion_bodies = set()
    calls_re = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
    for body_lines in comps.values():
        for ln in body_lines:
            for ref in calls_re.findall(ln):
                fusion_bodies.add(ref)

    for cname, body_lines in comps.items():
        mult = multiplier(cname)
        in_fusion = cname in fusion_bodies
        for ln in body_lines:
            dm = _DEF_RE.match(ln)
            if not dm:
                continue
            name = dm.group(1)
            dt, dims = sym.get(name, ("", []))
            # --- flops from dots (counted wherever they appear) ---
            dmatch = _DOT_RE.search(ln)
            if dmatch:
                ops_ = re.findall(r"%([\w.\-]+)", dmatch.group(1))
                cm = _CONTRACT_RE.search(ln)
                contract = 1
                if ops_ and cm:
                    lhs_dims = sym.get(ops_[0], ("", []))[1]
                    for ci in cm.group(1).split(","):
                        if ci and int(ci) < len(lhs_dims):
                            contract *= lhs_dims[int(ci)]
                res_elems = 1
                for d in dims:
                    res_elems *= d
                flops += 2.0 * res_elems * contract * mult
            # --- bytes at top level only ---
            if in_fusion or _SKIP_OPS.search(ln):
                continue
            res_b = _nbytes(dt, dims)
            op_bytes = []
            args = ln.split(" = ", 1)[1]
            paren = args.find("(")
            if paren >= 0:
                depth = 0
                end = paren
                for i, ch in enumerate(args[paren:], paren):
                    if ch == "(":
                        depth += 1
                    elif ch == ")":
                        depth -= 1
                        if depth == 0:
                            end = i
                            break
                for op in re.findall(r"%([\w.\-]+)", args[paren:end]):
                    odt, odims = sym.get(op, ("", []))
                    op_bytes.append(_nbytes(odt, odims))
            if "dynamic-update-slice" in ln or "dynamic_update_slice" in ln:
                # in-place update: traffic = the slice written (+read),
                # not the aliased full buffer
                small = sum(ob for ob in op_bytes if ob < res_b)
                b = 2 * max(small, 1)
            elif "dynamic-slice" in ln or "dynamic_slice" in ln:
                b = 2 * res_b           # read slice + write result
            else:
                b = res_b + sum(op_bytes)
            bytes_ += b * mult
    return {"flops": flops, "bytes": bytes_}


def collective_bytes(hlo_text: str) -> dict:
    """Sum collective *operand* bytes from optimized HLO (per-device
    program), accounting for while-loop (scan) trip counts.

    Operands are %name references; shapes come from a symbol table built
    over every defining line.  While bodies get a multiplier from the
    integer constant found in their condition computation (the scan bound).
    """
    lines = hlo_text.splitlines()

    # computation blocks + per-line symbol table of defined shapes
    comps: dict[str, list[str]] = {}
    sym_bytes: dict[str, int] = {}
    cur = None
    for line in lines:
        m = _HEADER_RE.match(line)
        if m and "->" in line:
            cur = m.group(1)
            comps[cur] = []
            continue
        if cur is not None:
            comps[cur].append(line)
        dm = _DEF_RE.match(line)
        if dm:
            head = line.split(" = ", 1)[1]
            shape_txt = head.split(" ", 1)[0] if " " in head else head
            sym_bytes[dm.group(1)] = _shape_bytes(shape_txt)

    # while ops -> trip counts from condition constants
    while_re = re.compile(r"while\(.*?\).*condition=%?([\w.\-]+).*"
                          r"body=%?([\w.\-]+)")
    trip: dict[str, int] = {}
    parent: dict[str, str] = {}
    for cname, body_lines in comps.items():
        for ln in body_lines:
            m = while_re.search(ln)
            if m:
                cond, body = m.group(1), m.group(2)
                parent[body] = cname
                t = 1
                for cl in comps.get(cond, []):
                    mc = re.search(r"constant\((\d+)\)", cl)
                    if mc:
                        t = max(t, int(mc.group(1)))
                trip[body] = t

    def multiplier(cname: str) -> int:
        mult = 1
        seen = set()
        while cname in trip and cname not in seen:
            seen.add(cname)
            mult *= trip[cname]
            cname = parent.get(cname, "")
        return mult

    per_kind: dict[str, float] = {}
    counts: dict[str, int] = {}
    total = 0
    for cname, body_lines in comps.items():
        mult = multiplier(cname)
        for ln in body_lines:
            m = _COLL_RE.search(ln)
            if not m:
                continue
            kind = m.group(1)
            args = ln[m.end():]
            depth = 1
            for i, ch in enumerate(args):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        args = args[:i]
                        break
            ops = re.findall(r"%([\w.\-]+)", args)
            b = sum(sym_bytes.get(o, 0) for o in ops)
            if b == 0:          # fallback: inline shapes in operand list
                b = _shape_bytes(args)
            per_kind[kind] = per_kind.get(kind, 0) + b * mult
            counts[kind] = counts.get(kind, 0) + mult
            total += b * mult
    per_kind["total"] = total
    per_kind["counts"] = counts
    return per_kind


def run(arch: str, shape: str, multi_pod: bool, out: str | None = None,
        **kw) -> dict:
    lowered, compiled, info = lower_cell(arch, shape, multi_pod, **kw)

    mem = compiled.memory_analysis()
    print("=== memory_analysis ===")
    print(mem)
    cost = compiled.cost_analysis() or {}
    print("=== cost_analysis (flops/bytes) ===")
    print({k: v for k, v in cost.items()
           if k in ("flops", "bytes accessed", "transcendentals")})

    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    print("=== collective bytes (per device program) ===")
    print(coll)
    hc = hlo_cost(hlo)
    print("=== hlo cost model (loop-aware, per device) ===")
    print(hc)
    comps, _, multiplier = _parse_hlo(hlo)
    seq_depth = max((multiplier(c) for c in comps), default=1)
    print(f"=== serialization: deepest loop-nest iterations = {seq_depth} ===")

    info.update({
        "flops": float(cost.get("flops", -1)),
        "bytes_accessed": float(cost.get("bytes accessed", -1)),
        "hlo_flops": hc["flops"],
        "hlo_bytes": hc["bytes"],
        "seq_depth": seq_depth,
        "collectives": coll,
    })
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        if mem is not None and hasattr(mem, attr):
            info[attr] = int(getattr(mem, attr))
    if out:
        with open(out, "w") as f:
            json.dump(info, f, indent=1)
    print("=== summary ===")
    print(json.dumps({k: v for k, v in info.items()
                      if k != "collectives"}, indent=1))
    return info


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--kv-compressed", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--sp", action="store_true")
    ap.add_argument("--mamba-chunked", action="store_true")
    args = ap.parse_args()
    if args.mamba_chunked:
        from repro.models import ssm as _ssm
        _ssm.CHUNKED_SCAN = True
    run(args.arch, args.shape, args.multi_pod, args.out,
        kv_compressed=args.kv_compressed, fsdp=not args.no_fsdp,
        microbatches=args.microbatches, sp=args.sp)


if __name__ == "__main__":
    main()
