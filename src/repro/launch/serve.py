"""Serving driver: batched greedy generation through the model API, the
LCP-paged compressed-KV engine (--paged), or the continuous-batching
scheduler loop (--scheduler).

The paged path runs the batched device-resident hot path end to end:
admission goes through ``PagedKVEngine.add_requests`` (one chunked-batch
prefill pass for all prompts, ``--prefill-chunk`` sets the step width)
and decode through ``decode_batch`` (one jitted step per token for the
whole batch); ``--paged-reference`` selects the seed host-looped engine
instead, for A/B timing.

``--scheduler`` drives the token-budget continuous-batching loop
(``serving/scheduler.py``): requests are submitted with staggered
arrivals (``--arrival-stagger`` iterations apart), admitted/retired
between iterations, and prefill chunks piggyback on decode steps under
``--token-budget``; the report adds per-request TTFT and latency in
scheduler iterations.  ``--prefix-cache`` attaches the SIP-guided
compressed prefix cache (``serving/prefix_cache.py``) so requests
sharing a prompt prefix share KV pages (pair with ``--shared-prefix N``
for a system-prompt workload; the per-request report shows cached
tokens), and ``--requeue-preempted`` turns CAMP preemptions into
recompute-from-prompt requeues instead of terminal retirements.

``--codec`` selects the KV page codec (``bdi`` | ``zero`` | ``raw``;
see ``repro.codecs``); every paged mode reports the aggregate and — in
scheduler mode — per-request compression ratio (raw vs device-reported
compressed bytes), labeled by codec name.

Resilience (scheduler mode; serving/faults.py): ``--ttft-deadline`` /
``--deadline`` set per-request deadlines in iterations, ``--max-queue``
bounds the waiting queue, ``--overload`` arms the pool-pressure
degradation ladder, and ``--chaos SEED`` injects a deterministic fault
schedule (page corruption + garbage decode tokens) — every request
still ends with a deterministic ``finish_reason``.  ``--tier-host-mb``
attaches the host/disk memory tier (``serving/tier.py``): evicted
prefix-cache chains demote into host RAM (optionally spilling to an
mmap disk arena via ``--tier-disk-dir``) and promote back on warm
lookups; ``--persist-cache DIR`` carries the warm cache across process
restarts, and ``--multi-turn N`` runs the chat scenario that recycles
the whole device pool between turns and reports per-turn TTFT.
``--snapshot-dir``
demos engine snapshot/restore: the engine state is checkpointed
mid-stream, then restored after the run and driven to completion; the
report's ``snapshot.restored_match`` confirms token-identical output.

``--smoke`` builds the arch's reduced config (``ArchConfig.reduced``);
without it the published config is built, full width and full depth.
``generate(n_layers=N)`` cuts the depth alone, for a run whose weights
and pools do not fit one device at full depth.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
      --prompt-len 16 --gen 16 [--paged | --paged-reference | --scheduler]
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib
import time

import jax
import jax.numpy as jnp

from repro.configs.registry import get_arch
from repro.models.api import get_model


_REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Put JAX's persistent compilation cache at a fixed path and return
    it: ``JAX_COMPILATION_CACHE_DIR`` where that is set (JAX reads it
    itself), else ``<repo>/.jax_cache``.  The path is part of the cache
    key, so it must not move between runs.  Call it from entry points,
    never at import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(_REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_info() -> dict:
    """The device the run is on, as JAX reports it."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def generate(arch: str, *, smoke: bool = False, batch: int = 4,
             prompt_len: int = 16, gen: int = 16,
             paged: bool = False, paged_reference: bool = False,
             prefill_chunk: int | None = None,
             scheduler: bool = False, token_budget: int = 64,
             arrival_stagger: int = 2, prefix_cache: bool = False,
             shared_prefix: int = 0,
             requeue_preempted: bool = False,
             codec: str | None = None,
             ttft_deadline: int | None = None,
             deadline: int | None = None,
             max_queue: int | None = None, overload: bool = False,
             chaos: int | None = None,
             snapshot_dir: str | None = None,
             trace_out: str | None = None,
             metrics: bool = False,
             metrics_port: int | None = None,
             metrics_out: str | None = None,
             metrics_jsonl: str | None = None,
             observatory: bool = False,
             audit_out: str | None = None,
             tier_host_mb: float | None = None,
             tier_disk_dir: str | None = None,
             persist_cache: str | None = None,
             multi_turn: int = 0,
             n_layers: int | None = None) -> dict:
    cfg = get_arch(arch)
    if smoke:
        cfg = cfg.reduced()
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    # 512 pages of every layer: a published page takes one pool id per layer
    pool_pages = 512 * cfg.n_layers
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)
    prompts = jax.random.randint(key, (batch, prompt_len), 1, cfg.vocab,
                                 jnp.int32)

    def _build_tier(eng):
        """Host/disk memory tier behind the device pool (serving/tier.py);
        restores a persisted warm cache when --persist-cache points at an
        existing checkpoint."""
        from repro.checkpoint import store as ckpt_store
        from repro.serving.tier import TieredPageStore
        host_mb = tier_host_mb if tier_host_mb else 64.0
        if (persist_cache is not None
                and ckpt_store.latest_step(persist_cache) is not None):
            tier = TieredPageStore.restore(
                persist_cache, cfg, eng.codec, host_mb=host_mb,
                disk_dir=tier_disk_dir)
        else:
            tier = TieredPageStore.for_model(
                cfg, eng.page, eng.codec, host_mb=host_mb,
                disk_dir=tier_disk_dir)
        eng.attach_tier(tier)
        return tier

    if multi_turn:
        # multi-turn chat scenario: one growing conversation, the device
        # pool fully recycled between turns.  Without the tier every turn
        # re-prefills from scratch; with it, turn N's prefix promotes
        # back from host RAM and TTFT collapses to the new-token tail.
        from repro.serving.engine import PagedKVEngine
        from repro.serving.prefix_cache import PrefixCache
        from repro.serving.telemetry import Telemetry

        tel = Telemetry()
        cache = PrefixCache.for_model(cfg, 8)
        eng = PagedKVEngine(cfg, params, page_size=8, n_pool_pages=pool_pages,
                            max_batch=1, prefill_chunk=prefill_chunk,
                            prefix_cache=cache, codec=codec, telemetry=tel,
                            cache_decode_pages=True)
        tier = _build_tier(eng)
        convo = [int(t) for t in prompts[0]]
        # throwaway primer turn: jit-compile prefill/decode so turn 1's
        # TTFT is not dominated by compilation
        eng.add_requests({-1: convo[: eng.page]})
        eng.decode_one(-1)
        eng.release(-1)
        eng.recycle_device_pool()
        base = dict(tier.stats)
        turns, total_toks, t_run = [], 0, time.perf_counter()
        for turn in range(1, multi_turn + 1):
            t0 = time.perf_counter()
            cached = eng.add_requests({turn: convo})[turn]
            out_toks = [eng.decode_one(turn)]
            ttft = time.perf_counter() - t0
            out_toks += [eng.decode_one(turn) for _ in range(gen - 1)]
            eng.release(turn)
            freed = eng.recycle_device_pool()
            d = {k: tier.stats[k] - base[k] for k in tier.stats}
            turns.append({"turn": turn, "prompt_tokens": len(convo),
                          "ttft_s": round(ttft, 4),
                          "cached_tokens": cached,
                          "recycled_pages": freed,
                          "demotions": d["demotions"],
                          "promotions": d["promotions"]})
            base = dict(tier.stats)
            total_toks += len(out_toks)
            # next user message: the model's reply plus fresh user tokens
            extra = jax.random.randint(jax.random.PRNGKey(100 + turn),
                                       (8,), 1, cfg.vocab)
            convo = convo + out_toks + [int(t) for t in extra]
        dt = time.perf_counter() - t_run
        eng.debug_validate()
        eng.sample_gauges()
        if persist_cache is not None:
            tier.persist(persist_cache)
        return {"turns": turns, "codec": eng.codec.name,
                "tier": dict(tier.stats),
                "tier_logical_bytes": tier.logical_bytes(),
                "kv_compression_ratio": eng.compression_ratio(),
                "stats": eng.stats, "tok_per_s": total_toks / dt,
                "persisted": persist_cache}

    if scheduler:
        from repro.core.camp import PressureLadder
        from repro.serving import faults as F
        from repro.serving.engine import PagedKVEngine
        from repro.serving.prefix_cache import PrefixCache
        from repro.serving.scheduler import ContinuousScheduler
        from repro.serving.telemetry import (Telemetry,
                                             start_metrics_server,
                                             stop_metrics_server)
        # one shared Telemetry: engine + scheduler write one registry,
        # one monotonic clock, one (optional) tracer
        tel = Telemetry(trace=trace_out is not None)
        obs = None
        if observatory or audit_out is not None:
            # hierarchy observatory: reuse tracking, shadow policy/codec
            # simulators, decision audit — all on the shared registry
            from repro.serving.observatory import Observatory
            obs = Observatory(tel)
        cache = (PrefixCache.for_model(cfg, 8) if prefix_cache else None)
        injector = None
        if chaos is not None:
            injector = F.FaultInjector(F.FaultSpec(
                corrupt_page_every=7, corrupt_max=2,
                garble_decode_every=11, garble_max=2), seed=chaos)
        eng = PagedKVEngine(cfg, params, page_size=8, n_pool_pages=pool_pages,
                            max_batch=batch, prefill_chunk=prefill_chunk,
                            prefix_cache=cache, codec=codec,
                            faults=injector, telemetry=tel,
                            observatory=obs)
        tier = None
        if tier_host_mb or tier_disk_dir or persist_cache:
            assert cache is not None, \
                "--tier-host-mb/--tier-disk-dir/--persist-cache need " \
                "--prefix-cache (the tier backs the prefix cache)"
            tier = _build_tier(eng)
        sched = ContinuousScheduler(eng, token_budget=token_budget,
                                    requeue_preempted=requeue_preempted,
                                    max_queue=max_queue,
                                    ladder=PressureLadder() if overload
                                    else None, telemetry=tel)
        server = None
        if metrics_port is not None:
            server = start_metrics_server([tel.registry], metrics_port)
            print(f"[serve] serving /metrics on port "
                  f"{server.server_address[1]}")
        for p in (trace_out, metrics_out, metrics_jsonl, audit_out):
            if p is not None and os.path.dirname(p):
                os.makedirs(os.path.dirname(p), exist_ok=True)
        jsonl_f = (open(metrics_jsonl, "w") if metrics_jsonl is not None
                   else None)
        # shared system prompt: every request reuses the first
        # ``shared_prefix`` prompt tokens (prefix-cache showcase)
        if shared_prefix:
            assert shared_prefix <= prompt_len, \
                (f"--shared-prefix {shared_prefix} exceeds --prompt-len "
                 f"{prompt_len}")
            sys_toks = prompts[0][:shared_prefix]
            prompts = jnp.concatenate(
                [jnp.tile(sys_toks[None], (batch, 1)),
                 prompts[:, shared_prefix:]], axis=1)
        arrivals = {b: b * arrival_stagger for b in range(batch)}
        t0 = tel.clock.now()
        pending = dict(arrivals)
        snap_step = None
        try:
            while pending or not sched.idle:
                if sched.iteration % 16 == 0:
                    eng.sample_gauges()   # keep exported gauges fresh
                    if jsonl_f is not None:
                        jsonl_f.write(tel.registry.to_jsonl_line(
                            iteration=sched.iteration) + "\n")
                for rid, at in list(pending.items()):
                    if at <= sched.iteration:
                        sched.submit(rid, [int(t) for t in prompts[rid]],
                                     max_new_tokens=gen,
                                     ttft_deadline=ttft_deadline,
                                     deadline=deadline)
                        del pending[rid]
                if snapshot_dir is not None and snap_step is None \
                        and not pending \
                        and (sched._running or sched._prefill):
                    # mid-stream snapshot with requests in flight: the
                    # restore demo below finishes them token-identically
                    from repro.serving.snapshot import save_snapshot
                    snap_step = sched.iteration
                    save_snapshot(snapshot_dir, eng, sched, step=snap_step)
                sched.step()
            dt = tel.clock.now() - t0
            eng.sample_gauges()
            if jsonl_f is not None:
                jsonl_f.write(tel.registry.to_jsonl_line(
                    iteration=sched.iteration, final=True) + "\n")
        finally:
            # clean exit or mid-run crash: release the file handle and
            # the metrics port (stop_metrics_server joins the thread)
            if jsonl_f is not None:
                jsonl_f.close()
            if server is not None:
                stop_metrics_server(server)
        if metrics_out is not None:
            with open(metrics_out, "w") as f:
                f.write(tel.registry.to_prometheus())
        if trace_out is not None:
            tel.tracer.write_chrome_trace(trace_out)
        eng.debug_validate()
        fin = sched.finished()
        outs = [fin[b].out_tokens for b in range(batch)]
        # first_token_iter stays None when a request retires preempted
        # before emitting anything (e.g. past the requeue limit)
        def req_ratio(b):
            raw, comp = eng.request_bytes.get(b, (0, 0))
            return round(raw / comp, 3) if comp else None

        report = {b: {"ttft_iters": (fin[b].first_token_iter - arrivals[b]
                                     if fin[b].first_token_iter is not None
                                     else None),
                      "latency_iters": fin[b].finished_iter - arrivals[b],
                      "cached_tokens": fin[b].pf_start,
                      "compression_ratio": req_ratio(b),
                      "reason": str(fin[b].finish_reason)}
                  for b in range(batch)}
        out = {"tokens": outs, "codec": eng.codec.name,
               "device": device_info(), "n_layers": cfg.n_layers,
               "use_fused": eng.use_fused,
               "use_fused_fill": eng.use_fused_fill,
               "kv_compression_ratio": eng.compression_ratio(),
               "stats": eng.stats,
               "sched_stats": sched.stats, "per_request": report,
               "tok_per_s": sum(len(o) for o in outs) / dt}
        if injector is not None:
            out["faults"] = dict(injector.stats, log=injector.log)
        if cache is not None:
            out["prefix_cache"] = dict(cache.stats,
                                       hit_rate=round(cache.hit_rate(), 3))
        if tier is not None:
            out["tier"] = dict(tier.stats)
            if persist_cache is not None:
                tier.persist(persist_cache)
                out["persisted"] = persist_cache
        if metrics or metrics_out is not None or trace_out is not None:
            out["metrics_summary"] = _metrics_summary(tel, eng, sched)
        if obs is not None:
            out["observatory"] = obs.summary()
            out["reuse_table"] = obs.reuse_table()
            if audit_out is not None:
                obs.audit.to_jsonl(audit_out)
        if snap_step is not None:
            # restore the mid-stream snapshot into a fresh engine and
            # drive it to drain: outputs must match the original run
            from repro.serving.snapshot import restore_snapshot
            eng2, sched2 = restore_snapshot(snapshot_dir, cfg, params,
                                            step=snap_step)
            fin2 = sched2.run()
            match = all(fin2[b].out_tokens == fin[b].out_tokens
                        and str(fin2[b].finish_reason)
                        == str(fin[b].finish_reason) for b in fin2)
            eng2.debug_validate()
            out["snapshot"] = {"step": snap_step, "restored_match": match,
                               "restored_requests": len(fin2)}
        return out

    if paged or paged_reference:
        reqs = {b: [int(t) for t in prompts[b]] for b in range(batch)}
        t0 = time.perf_counter()
        if paged_reference:
            from repro.serving.reference import ReferencePagedKVEngine
            eng = ReferencePagedKVEngine(cfg, params, page_size=8,
                                         n_pool_pages=pool_pages, codec=codec)
            eng.add_requests(reqs)
            for _ in range(gen):
                for b in range(batch):
                    eng.decode_one(b)
        else:
            from repro.serving.engine import PagedKVEngine
            eng = PagedKVEngine(cfg, params, page_size=8,
                                n_pool_pages=pool_pages, max_batch=batch,
                                prefill_chunk=prefill_chunk,
                                codec=codec)
            eng.add_requests(reqs)      # one chunked-batch prefill pass
            for _ in range(gen):
                eng.decode_batch()
        dt = time.perf_counter() - t0
        outs = [eng.seqs[b].tokens[prompt_len:] for b in range(batch)]
        return {"tokens": outs, "codec": eng.codec.name,
                "kv_compression_ratio": eng.compression_ratio(),
                "stats": eng.stats,
                "tok_per_s": batch * gen / dt}

    max_len = prompt_len + gen
    batch_d = {"tokens": prompts}
    if cfg.is_encdec:
        batch_d["enc_embeds"] = (jax.random.normal(
            key, (batch, prompt_len, cfg.d_model)) * 0.02)
    t0 = time.perf_counter()
    logits, cache = model.prefill(params, batch_d, max_len)
    toks = jnp.argmax(logits, -1)
    out = [toks]
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    for t in range(prompt_len, prompt_len + gen - 1):
        logits, cache = step(params, cache, toks, jnp.int32(t))
        toks = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(toks)
    dt = time.perf_counter() - t0
    gen_toks = jnp.stack(out, axis=1)
    return {"tokens": gen_toks.tolist(), "tok_per_s": batch * gen / dt}


def _metrics_summary(tel, eng, sched) -> dict:
    """End-of-run summary table data (--metrics): per-codec ratio, TTFT
    percentiles, inter-token latency, ladder transitions — read from
    the shared registry's histograms, not recomputed ad hoc."""
    reg = tel.registry
    per_codec = {}
    for labels, pages in reg.series("engine_pages_by_codec_total"):
        name = labels["codec"]
        ratios = [m for lb, m in
                  reg.series("engine_page_compression_ratio")
                  if lb["codec"] == name]
        per_codec[name] = {
            "pages": pages.value,
            "ratio_p50": round(ratios[0].quantile(0.5), 3) if ratios
            else None}

    def pct(name):
        hs = [m for _, m in reg.series(name)]
        if not hs or hs[0].count == 0:
            return None
        h = hs[0]
        return {"p50": round(h.quantile(0.5), 4),
                "p95": round(h.quantile(0.95), 4),
                "p99": round(h.quantile(0.99), 4), "n": h.count}

    return {"ttft_s": pct("serve_ttft_seconds"),
            "intertoken_s": pct("serve_intertoken_seconds"),
            "latency_s": pct("serve_request_latency_seconds"),
            "dispatch_s": pct("sched_dispatch_seconds"),
            "per_codec": per_codec,
            "ladder_transitions": sched.stats["ladder_transitions"],
            "pool_used_pages": eng.pool_used_pages()}


_EPILOG = """\
observability (scheduler mode):
  --metrics            print an end-of-run summary: TTFT / inter-token /
                       latency percentiles (from the registry's streaming
                       histograms), per-codec page counts and ratio, ladder
                       transitions, pool occupancy
  --trace-out PATH     write the run's Chrome trace_event timeline; open it
                       at https://ui.perfetto.dev (or chrome://tracing) to
                       scrub per-request spans + per-iteration counters
  --metrics-port N     serve Prometheus text on http://127.0.0.1:N/metrics
                       for the duration of the run (0 = ephemeral port)
  --metrics-out PATH   write one final Prometheus text snapshot
  --metrics-jsonl PATH append JSON-lines registry snapshots every 16
                       iterations (one object per line, `ts` + `metrics`)
  --observatory        attach the memory-hierarchy observatory: live
                       size-bin x reuse-distance histograms, shadow
                       retention-policy / single-codec simulators, and
                       the decision audit log; the report adds shadow
                       hit rates and the joint reuse table
  --audit-out PATH     write the decision audit log (SIP evictions, CAMP
                       preemptions, ladder transitions, admission
                       rejections + driving inputs) as JSONL; implies
                       --observatory
See src/repro/serving/README.md ("Observability") for the metrics
reference table, audit schema, and trace schema; render saved artifacts
with `python -m repro.launch.observe`.
"""


def main() -> None:
    ap = argparse.ArgumentParser(
        epilog=_EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="run the arch's reduced config instead of the "
                         "published one")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--paged", action="store_true")
    ap.add_argument("--paged-reference", action="store_true",
                    help="seed host-looped engine (A/B baseline)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill step width in tokens "
                         "(page-aligned; default 2x page size)")
    ap.add_argument("--scheduler", action="store_true",
                    help="continuous-batching token-budget loop")
    ap.add_argument("--token-budget", type=int, default=64,
                    help="per-iteration token budget (scheduler mode)")
    ap.add_argument("--arrival-stagger", type=int, default=2,
                    help="iterations between request arrivals "
                         "(scheduler mode)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="SIP-guided compressed prefix cache: share "
                         "prompt-prefix KV pages across requests "
                         "(scheduler mode)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="make every request share its first N prompt "
                         "tokens (system-prompt workload; scheduler mode)")
    ap.add_argument("--requeue-preempted", action="store_true",
                    help="CAMP-preempted requests re-enter the queue "
                         "with recompute-from-prompt instead of retiring")
    ap.add_argument("--codec", default=None,
                    help="KV page codec (bdi | zero | raw | gbdi | fpc "
                         "| adaptive; default: REPRO_CODEC env or bdi)")
    ap.add_argument("--ttft-deadline", type=int, default=None,
                    help="per-request TTFT deadline in scheduler "
                         "iterations (scheduler mode)")
    ap.add_argument("--deadline", type=int, default=None,
                    help="per-request total deadline in scheduler "
                         "iterations (scheduler mode)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded waiting queue: submissions past this "
                         "depth finish 'rejected' (scheduler mode)")
    ap.add_argument("--overload", action="store_true",
                    help="arm the pool-pressure degradation ladder "
                         "(shed cache inserts -> shrink prefill share "
                         "-> reject admissions; scheduler mode)")
    ap.add_argument("--chaos", type=int, default=None,
                    help="fault-injection seed: deterministic page "
                         "corruption + garbage decode tokens "
                         "(scheduler mode)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="snapshot the engine mid-stream into this dir, "
                         "then restore and verify token-identical "
                         "completion (scheduler mode)")
    ap.add_argument("--trace-out", default=None,
                    help="write the Chrome/Perfetto trace_event timeline "
                         "here (scheduler mode; see epilog)")
    ap.add_argument("--metrics", action="store_true",
                    help="print an end-of-run metrics summary table "
                         "(scheduler mode; see epilog)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus text on this port during the "
                         "run (scheduler mode; 0 = ephemeral)")
    ap.add_argument("--metrics-out", default=None,
                    help="write a final Prometheus text snapshot here "
                         "(scheduler mode)")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append JSON-lines registry snapshots here "
                         "(scheduler mode)")
    ap.add_argument("--observatory", action="store_true",
                    help="attach the memory-hierarchy observatory "
                         "(scheduler mode; see epilog)")
    ap.add_argument("--audit-out", default=None,
                    help="write the decision audit log as JSONL here "
                         "(scheduler mode; implies --observatory)")
    ap.add_argument("--tier-host-mb", type=float, default=None,
                    help="attach the host-RAM memory tier behind the "
                         "device pool with this arena budget; evicted "
                         "prefix-cache chains demote here instead of "
                         "dropping (needs --prefix-cache in scheduler "
                         "mode)")
    ap.add_argument("--tier-disk-dir", default=None,
                    help="add an mmap-backed disk arena under this dir; "
                         "host-arena evictions spill there instead of "
                         "dropping")
    ap.add_argument("--persist-cache", default=None,
                    help="persist the tier through the checkpoint store "
                         "into this dir at exit, and restore from it at "
                         "start when it already holds a checkpoint "
                         "(warm cache across restarts)")
    ap.add_argument("--multi-turn", type=int, default=0,
                    help="multi-turn chat scenario: N turns of one "
                         "growing conversation with the device pool "
                         "fully recycled between turns; reports per-turn "
                         "TTFT and tier demotion/promotion counts")
    args = ap.parse_args()
    enable_compile_cache()
    out = generate(args.arch, smoke=args.smoke, batch=args.batch,
                   prompt_len=args.prompt_len,
                   gen=args.gen, paged=args.paged,
                   paged_reference=args.paged_reference,
                   prefill_chunk=args.prefill_chunk,
                   scheduler=args.scheduler, token_budget=args.token_budget,
                   arrival_stagger=args.arrival_stagger,
                   prefix_cache=args.prefix_cache,
                   shared_prefix=args.shared_prefix,
                   requeue_preempted=args.requeue_preempted,
                   codec=args.codec, ttft_deadline=args.ttft_deadline,
                   deadline=args.deadline, max_queue=args.max_queue,
                   overload=args.overload, chaos=args.chaos,
                   snapshot_dir=args.snapshot_dir,
                   trace_out=args.trace_out, metrics=args.metrics,
                   metrics_port=args.metrics_port,
                   metrics_out=args.metrics_out,
                   metrics_jsonl=args.metrics_jsonl,
                   observatory=args.observatory,
                   audit_out=args.audit_out,
                   tier_host_mb=args.tier_host_mb,
                   tier_disk_dir=args.tier_disk_dir,
                   persist_cache=args.persist_cache,
                   multi_turn=args.multi_turn)
    print(f"[serve] {args.batch}x{args.gen} tokens at "
          f"{out['tok_per_s']:.1f} tok/s")
    if "turns" in out:
        for trn in out["turns"]:
            print(f"[serve]   turn {trn['turn']}: "
                  f"{trn['prompt_tokens']}-token prompt, ttft "
                  f"{trn['ttft_s'] * 1000:.1f} ms, "
                  f"{trn['cached_tokens']} cached, "
                  f"{trn['recycled_pages']} pages recycled, "
                  f"demote {trn['demotions']} promote {trn['promotions']}")
    if "kv_compression_ratio" in out:
        print(f"[serve] codec {out['codec']}: aggregate compression "
              f"{out['kv_compression_ratio']:.2f}x (raw/compressed "
              f"device-reported bytes); stats: {out['stats']}")
    if "sched_stats" in out:
        print(f"[serve] scheduler: {out['sched_stats']}")
        for rid, r in out["per_request"].items():
            ratio = r["compression_ratio"]
            print(f"[serve]   req {rid}: ttft {r['ttft_iters']} iters, "
                  f"latency {r['latency_iters']} iters, "
                  f"{r['cached_tokens']} cached, "
                  f"{out['codec']} ratio "
                  f"{'n/a' if ratio is None else f'{ratio:.2f}x'} "
                  f"({r['reason']})")
    if "metrics_summary" in out:
        ms = out["metrics_summary"]
        print("[serve] metrics summary:")
        for k in ("ttft_s", "intertoken_s", "latency_s", "dispatch_s"):
            v = ms[k]
            if v is not None:
                print(f"[serve]   {k:<13} p50 {v['p50']}  p95 {v['p95']}  "
                      f"p99 {v['p99']}  (n={v['n']})")
        for name, pc in ms["per_codec"].items():
            print(f"[serve]   codec {name}: {pc['pages']} pages, "
                  f"page-ratio p50 {pc['ratio_p50']}")
        print(f"[serve]   ladder transitions {ms['ladder_transitions']}, "
              f"pool used {ms['pool_used_pages']} pages")
    if "observatory" in out:
        ob = out["observatory"]
        print(f"[serve] observatory: shadow hit rates "
              f"{ob['shadow_hit_rates']}")
        print(f"[serve]   live pages {ob['live_pages']}, reuse ticks "
              f"{ob['reuse_ticks']}, audit decisions "
              f"{ob['audit_decisions']}")
        if ob["codec_wouldbe_bytes"]:
            print(f"[serve]   single-codec what-if bytes: "
                  f"{ob['codec_wouldbe_bytes']}")
        print("[serve] size-bin x reuse-distance:")
        for ln in out["reuse_table"].splitlines():
            print(f"[serve]   {ln}")
    if "faults" in out:
        print(f"[serve] injected faults: {out['faults']}")
    if "prefix_cache" in out:
        print(f"[serve] prefix cache: {out['prefix_cache']}")
    if "tier" in out:
        print(f"[serve] memory tier: {out['tier']}")
        if out.get("persisted"):
            print(f"[serve] tier persisted to {out['persisted']}")
    if "snapshot" in out:
        print(f"[serve] snapshot/restore: {out['snapshot']}")


if __name__ == "__main__":
    main()
