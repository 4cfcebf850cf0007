"""Build the serving stack a cell names, warm up every shape its traffic
uses, and drive it through the measured window.

What is built is what ``repro.launch.serve.generate`` builds for its
scheduler mode: ``PagedKVEngine`` with a ``PrefixCache`` and a
``ContinuousScheduler``.  The window loop submits each request once its
due time has passed on the wall clock, calls ``sched.step()``, and
timestamps every admitted, decoded and retired event the step returns.

Warm-up drives the engine only through its public methods
(``begin_cohort``, ``mixed_step``, ``abort``, ``release``,
``recycle_device_pool``) with requests of its own, shaped so that every
shape bucket the window can reach is run once.
"""

from __future__ import annotations

import dataclasses
import heapq
import logging
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig
from repro.serving.engine import PagedKVEngine
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import ContinuousScheduler

from . import traffic, weights
from .spec import Cell, model_dims

WARM_SID = 1 << 40          # warm-up request ids, clear of the window's
WARM_STREAM = 0x3A4B        # the fixed stream warm-up tokens come from


def _pow2(n: int) -> int:
    c = 1
    while c < n:
        c *= 2
    return c


class CompileWatch:
    """Records the name of every program JAX compiles or loads from its
    persistent cache (``jax_log_compiles``), without printing them."""

    def __init__(self):
        self.names: list[str] = []
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_listener(self._on_event)
        jax.config.update("jax_log_compiles", True)
        for name in ("jax._src.dispatch", "jax._src.compiler",
                     "jax._src.compilation_cache", "jax._src.cache_key"):
            logging.getLogger(name).setLevel(logging.ERROR)
        log = logging.getLogger("jax._src.interpreters.pxla")
        log.addHandler(_Names(self.names))
        log.propagate = False

    @property
    def n(self) -> int:
        return len(self.names)

    def _on_event(self, event: str, **_) -> None:
        for k in self.cache:
            if event == f"/jax/compilation_cache/cache_{k}":
                self.cache[k] += 1


_WATCH: list[CompileWatch] = []


def compile_watch() -> CompileWatch:
    """The process's one watch (its listeners are registered once)."""
    if not _WATCH:
        _WATCH.append(CompileWatch())
    return _WATCH[0]


class _Names(logging.Handler):
    def __init__(self, names):
        super().__init__(logging.WARNING)
        self.names = names

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split()[1])


@dataclasses.dataclass
class Stack:
    cfg: ArchConfig
    eng: PagedKVEngine
    sched: ContinuousScheduler


def arch_config(cell: Cell) -> ArchConfig:
    d = model_dims(cell.config)
    return ArchConfig(name=cell.cell["config"], family="dense",
                      n_layers=d["n_layers"], d_model=d["d_model"],
                      n_heads=d["n_heads"], n_kv_heads=d["n_kv_heads"],
                      d_ff=d["d_ff"], vocab=d["vocab"],
                      head_dim=d["head_dim"], qkv_bias=d["qkv_bias"],
                      rope_theta=d["rope_theta"], norm_eps=d["norm_eps"])


def build(cell: Cell, seed: int) -> Stack:
    cfg = arch_config(cell)
    e = cell.cell["engine"]
    params = weights.model(model_dims(cell.config), seed)
    cache = (PrefixCache.for_model(cfg, e["page_size"])
             if cell.cell["prefix_cache"] else None)
    eng = PagedKVEngine(cfg, params, page_size=e["page_size"],
                        n_pool_pages=e["n_pool_pages"],
                        max_batch=e["max_batch"],
                        prefill_chunk=e["prefill_chunk"],
                        prefix_cache=cache, codec=cell.cell["codec"])
    sched = ContinuousScheduler(eng, token_budget=e["token_budget"])
    return Stack(cfg, eng, sched)


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

def tmax_of(stored: int, chunk: int) -> int:
    """The cohort scratch length the engine picks for a longest stored
    prompt (prompt minus its last token) of ``stored`` tokens."""
    return _pow2(-(-stored // chunk) + 1) * chunk


def stored_range(tmax: int, chunk: int) -> tuple[int, int]:
    """Longest stored prompts, inclusive, that give scratch length
    ``tmax``."""
    c = tmax // chunk
    return max(1, (c // 2 - 1) * chunk + 1), (c - 1) * chunk


def shape_plan(cell: Cell) -> dict:
    """The shape buckets the window's traffic can reach.

    The engine rounds the page-table width (PMAX), a cohort's rows and
    its scratch length up to powers of two, and runs one program for
    each count of pages it publishes or tails it writes in a step.
    """
    e, mix = cell.cell["engine"], cell.mix
    page, chunk, b = e["page_size"], e["prefill_chunk"], e["max_batch"]
    lo, hi = traffic.fresh_prompt_range(mix)
    return {"page": page, "chunk": chunk, "batch": b,
            "pmax": _pow2(-(-traffic.max_context(mix) // page)),
            "rows": sorted({min(_pow2(n), b) for n in range(1, b + 1)}),
            "tmax": sorted({tmax_of(s, chunk) for s in range(lo - 1, hi)}),
            "stored": (lo - 1, hi - 1),
            "hit_pages": traffic.max_hit_tokens(mix) // page}


def _advance(stored, pub, done, roff, n, page):
    """The engine's cohort bookkeeping for an ``n``-token advance of rows
    with no cached prefix: pages published and tails written (rows whose
    prefill ends, with a partial last page)."""
    new = roff + n
    m = w = 0
    for i, s in enumerate(stored):
        upto = min(new, s) // page
        m += max(0, upto - pub[i])
        pub[i] = max(pub[i], upto)
        if i not in done and new >= s:
            done.add(i)
            w += bool(s % page)
    return m, w


def sweep_cohorts(rows: int, tmax: int, plan: dict,
                  rng: np.random.Generator) -> list[tuple[list, list]]:
    """Cohorts of ``rows`` prompts at scratch length ``tmax``, each with
    the chunk advances to run, that together publish every page count
    from 1 to ``rows`` chunks' worth in one step and write every count of
    tails from 1 to ``rows``."""
    page, chunk = plan["page"], plan["chunk"]
    a, b = stored_range(tmax, chunk)
    a, b = max(a, plan["stored"][0]), min(b, plan["stored"][1])
    want_m = set(range(1, rows * chunk // page + 1))
    want_w = set(range(1, rows + 1))
    out = []
    for _ in range(4000):
        if not (want_m or want_w):
            break
        top = int(rng.integers(a, b + 1))      # rows spread, or bunched
        low = 1 if len(out) % 2 else max(1, top - chunk + 1)
        stored = [top] + [int(x) for x in rng.integers(low, top + 1,
                                                       rows - 1)]
        pub, done, roff, steps, gain = [0] * rows, set(), 0, [], 0
        while roff < max(stored):
            best = None
            for n in range(chunk, 0, -page):
                n = min(n, max(stored) - roff)
                m, w = _advance(stored, list(pub), set(done), roff, n,
                                page)
                key = ((m in want_m) + (w in want_w), n)
                if best is None or key > best[0]:
                    best = (key, n)
            n = best[1]
            m, w = _advance(stored, pub, done, roff, n, page)
            gain += (m in want_m) + (w in want_w)
            want_m.discard(m)
            want_w.discard(w)
            roff += n
            steps.append(n)
        if gain:
            out.append((stored, steps))
    return out


def tail_rounds(batch: int) -> list[list[int]]:
    """Groups of rows that fill their decode tails in the same step, so
    that every count from 1 to ``batch`` fills at once in some step."""
    want, out = set(range(1, batch + 1)), []
    while want:
        room, groups = batch, []
        for g in sorted(want, reverse=True):
            if g <= room:
                groups.append(g)
                room -= g
        want -= set(groups)
        out.append(groups)
    return out


class _Warm:
    """Requests of the warm-up's own, through the engine's public
    methods."""

    def __init__(self, eng: PagedKVEngine, vocab: int):
        self.eng = eng
        self.rng = np.random.default_rng(WARM_STREAM)
        self.vocab = vocab
        self.sid = WARM_SID

    def tokens(self, n: int) -> list[int]:
        return self.rng.integers(1, self.vocab, size=n).tolist()

    def cohort(self, prompts: list[list[int]]) -> list[int]:
        sids = list(range(self.sid, self.sid + len(prompts)))
        self.sid += len(prompts)
        self.eng.begin_cohort(dict(zip(sids, prompts)))
        return sids

    def run(self, sids: list[int], steps: list[int] | None = None,
            decode: int = 0, keep: bool = False,
            alongside: bool = True) -> None:
        """Advance the cohort by ``steps`` (full chunks until its prefill
        ends where None), decoding every decodable sequence alongside
        (unless not ``alongside``), then ``decode`` decode-only steps,
        then retire ``sids`` (unless ``keep``)."""
        eng = self.eng
        dec = None if alongside else []
        for n in steps or []:
            eng.mixed_step(dec, pf_tokens=n)
        while steps is None and any(eng.seqs[s].prefilling for s in sids):
            eng.mixed_step(dec, pf_tokens=eng.prefill_chunk)
        for _ in range(decode):
            eng.mixed_step(pf_tokens=0)
        for s in [] if keep else sids:
            eng.release(s)

    def drop(self, sids: list[int]) -> None:
        for s in sids:
            self.eng.abort(s)
            self.eng.release(s)


def warm(st: Stack, plan: dict) -> None:
    """Run every shape bucket of the plan once, then empty the prefix
    cache, so that the window compiles nothing."""
    eng, w = st.eng, _Warm(st.eng, st.cfg.vocab)
    page, chunk = plan["page"], plan["chunk"]
    lo_s, hi_s = plan["stored"]
    # the page-table width only grows: one long request sets it to the
    # width the longest context reaches, and runs the decode step there
    if plan["pmax"] > 8:
        w.run(w.cohort([w.tokens((plan["pmax"] // 2 + 1) * page + 1)]),
              decode=1)
    # prefix hits of every depth bucket fill the scratch from the pool
    # (and verify the hit pages' checksums) at admission
    hp = plan["hit_pages"]
    if hp:
        head = w.tokens(hp * page)
        w.run(w.cohort([head + w.tokens(1)]))
        for k in sorted({_pow2(k) for k in range(1, hp + 1)} | {hp}):
            k = min(k, hp)
            for t in plan["tmax"]:
                s = min(stored_range(t, chunk)[1], hi_s)
                if s <= k * page:
                    continue
                for r in plan["rows"]:
                    w.drop(w.cohort(
                        [head[:k * page] + w.tokens(s + 1 - k * page)]
                        + [w.tokens(s + 1) for _ in range(r - 1)]))
    # every cohort shape alone, with every count of pages published and
    # tails written in a step.  Rows that end their prefill early do not
    # decode while the cohort is in flight: the engine would publish
    # their next filled tail page a second time, as a prompt page.
    for r in plan["rows"]:
        for t in plan["tmax"]:
            for stored, steps in sweep_cohorts(r, t, plan, w.rng):
                w.run(w.cohort([w.tokens(s + 1) for s in stored]), steps,
                      alongside=False)
    # every cohort shape beside decodes: beside a request of its own for
    # one step where a slot is free, else beside its own one-token rows,
    # whose tails stay short of a page while the cohort runs
    for r in plan["rows"]:
        for t in plan["tmax"]:
            s = max(stored_range(t, chunk)[0], lo_s)
            if r < plan["batch"]:
                dec = w.cohort([w.tokens(2)])
                w.run(dec, keep=True)
                co = w.cohort([w.tokens(s + 1) for _ in range(r)])
                w.run(co, [chunk], keep=True)
                w.run(dec, [])
                w.run(co, alongside=False)
            elif r > 1 and -(-(s - 1) // chunk) < page - 1:
                w.run(w.cohort([w.tokens(s + 1)]
                               + [w.tokens(2) for _ in range(r - 1)]),
                      [1] + [chunk] * -(-(s - 1) // chunk))
    # every count of decode tails that fill in the same step: group g's
    # rows fill after g + 1 decode steps
    base = -(-max(lo_s, 1) // page) * page
    assert base + page - 1 <= hi_s, (base, hi_s)
    for groups in tail_rounds(plan["batch"]):
        stored = [base + page - 1 - g for g, n in enumerate(groups)
                  for _ in range(n)]
        w.run(w.cohort([w.tokens(s + 1) for s in stored]),
              decode=len(groups), alongside=False)
    eng.recycle_device_pool()
    jax.block_until_ready((eng.pools, eng.tail_k, eng.tail_v))


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StepRec:
    t0: float
    t1: float
    decode_ctx: list[int]       # context (new token included) per row
    prefill_n: int              # prompt tokens prefilled
    prefill_pos_sum: int        # sum of their 0-based positions


class LoadGen:
    """Submits requests at their due times and records what each
    ``sched.step()`` returns, on the host's monotonic clock.  A finished
    request's follow-up (the session's next turn, a closed-loop client's
    next session) is due its delay after the finish."""

    def __init__(self, st: Stack, tr: traffic.Traffic):
        self.st = st
        self.tr = tr
        self.reqs: dict[int, traffic.Req] = {}
        self.pending: list[tuple[float, int, traffic.Req]] = []
        self.steps: list[StepRec] = []
        self.retired: dict[int, str] = {}
        self.clock = time.perf_counter

    def add(self, r: traffic.Req, due_abs: float) -> None:
        heapq.heappush(self.pending, (due_abs, r.rid, r))

    def _submit_due(self, now: float) -> None:
        while self.pending and self.pending[0][0] <= now:
            due, _, r = heapq.heappop(self.pending)
            r.due_abs = due
            self.reqs[r.rid] = r
            self.st.sched.submit(r.rid, r.prompt, max_new_tokens=r.max_new)

    def step(self, t_end: float) -> dict | None:
        """Submit what is due, then run one scheduler step; with nothing
        to run, wait for the next due time (or ``t_end``)."""
        now = self.clock()
        with TraceAnnotation("bench.submit"):
            self._submit_due(now)
        sched = self.st.sched
        if sched.idle:
            until = min(self.pending[0][0], t_end) if self.pending else t_end
            with TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, until - self.clock()))
            return None
        pf_before = {rid: tr.pf_pos for rid, tr in sched.tracks.items()
                     if tr.state == "prefill"}
        t0 = self.clock()
        with TraceAnnotation("bench.step"):
            ev = sched.step()
        t1 = self.clock()
        with TraceAnnotation("bench.record"):
            self._record(ev, t0, t1, pf_before)
        return ev

    def _record(self, ev, t0, t1, pf_before) -> None:
        tracks = self.st.sched.tracks
        for rid in ev["admitted"]:
            self.reqs[rid].admit_t = t1
        ctx = []
        for rid, tok in ev["decoded"].items():
            r = self.reqs[rid]
            r.token_t.append(t1)
            r.out.append(tok)
            ctx.append(len(r.prompt) + len(r.out) - 1)
        n = pos = 0
        for rid in set(pf_before) | set(ev["admitted"]):
            tr = tracks[rid]
            a = pf_before.get(rid, tr.pf_start)
            n += tr.pf_pos - a
            pos += (a + tr.pf_pos - 1) * (tr.pf_pos - a) // 2
        self.steps.append(StepRec(t0, t1, ctx, n, pos))
        for rid, reason in ev["retired"]:
            self.retired[rid] = str(reason)
            nxt = self.tr.follow(self.reqs[rid])
            if nxt is not None:
                self.add(nxt[0], t1 + nxt[1])

    def run_until(self, t_end: float) -> None:
        while self.clock() < t_end:
            self.step(t_end)


def preroll(st: Stack, cell: Cell, seed: int, seconds: float) -> LoadGen:
    """Start the cell's traffic so that the window opens on a loaded
    system.  Returns the load generator with ``t_open`` set: the window is
    [t_open, t_open + seconds)."""
    tr = traffic.Traffic(cell.mix, seed, st.cfg.vocab, seconds)
    d = LoadGen(st, tr)
    if tr.closed:
        for r in tr.initial:       # one at a time: a resumed session's
            d.add(r, d.clock())    # prompt needs a long scratch row
            while st.sched.tracks.get(r.rid) is None \
                    or st.sched.tracks[r.rid].state in ("waiting",
                                                        "prefill"):
                d.step(0.0)        # never idle here: no wait
        t_open = d.clock()
    else:
        t_open = d.clock() + tr.preroll_s
        for r in tr.initial:
            d.add(r, t_open + r.due)
        d.run_until(t_open)
    d.t_open = t_open
    return d


def relative(d: LoadGen) -> list[traffic.Req]:
    """Requests with times in seconds from the window's opening, those
    due but not yet submitted when the loop stopped among them."""
    out = []
    for due, _, r in d.pending:
        r.due_abs = due
    for r in list(d.reqs.values()) + [p[2] for p in d.pending]:
        r.due = r.due_abs - d.t_open
        r.token_t = [t - d.t_open for t in r.token_t]
        r.admit_t = None if r.admit_t is None else r.admit_t - d.t_open
        out.append(r)
    for s in d.steps:
        s.t0 -= d.t_open
        s.t1 -= d.t_open
    return out
