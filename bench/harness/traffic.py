"""The one traffic generator.  A mix is a data file of parameters
(``bench/traffic/<mix>.json``) with the same keys for every mix:

``arrival``
    ``{"kind": "poisson", "rate_per_s", "preroll_s", "phases"}``: sessions
    start at a mean rate, modulated by ``phases`` (``[[seconds,
    multiplier], ...]``, repeated; ``[[1, 1]]`` is steady), from
    ``preroll_s`` before the window opens.  Or ``{"kind": "closed",
    "clients", "resume"}``: each client starts its next session when its
    last one ends; with ``resume`` each client's first session is picked
    up part-way through its output, so the window opens on page lists
    spread as in steady serving.
``classes``
    ``[{"share", "prefix": {"groups", "tokens", "zipf_s"},
    "prompt_tokens", "output_tokens", "turns", "think_s"}, ...]``: each
    session belongs to one class by ``share``.  Its prompts start with
    the ``tokens`` of one of ``groups`` shared prefixes (Zipf popularity;
    ``groups`` 0 shares nothing), followed by a part of its own.  A
    session of several ``turns`` sends each next turn ``think_s`` after
    the last one ends, its prompt the whole conversation so far plus a
    new part.  Lengths are lognormal (``median``, ``sigma``, clipped to
    ``min``..``max``).

Every seed gets the same schedule: the same lengths, gaps, classes,
prefix groups and resume ages (quantiles of their distributions) in the
same order, drawn from a fixed stream.  The seed draws the token ids and
the weights.  A window holds about sixteen requests at yi-6b's knee, so
an order drawn from the seed would move the median time to first token
by a fifth to two fifths, and the tokens per second by a sixth, from seed
to seed: more than a bound may allow.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np

CLOSED_POOL = 8     # sessions drawn per closed-loop client, reused in turn
SCHEDULE = 0x5C4ED  # the fixed stream the schedule is drawn from


@dataclass
class Req:
    rid: int
    prompt: list[int]
    max_new: int
    due: float = 0.0          # seconds from the window's opening
    session: int = -1
    turn: int = 0
    client: int = -1
    out: list[int] = field(default_factory=list)       # served tokens
    token_t: list[float] = field(default_factory=list)  # their times
    admit_t: float | None = None
    due_abs: float = 0.0


@dataclass
class _Session:
    cls: dict
    head: list[int]           # the shared prefix (empty for none)
    prompt_len: list[int]     # own part of each turn
    out_len: list[int]        # output of each turn


def lognormal_quantiles(n: int, spec: dict) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of a lognormal with
    the spec's median and sigma, rounded and clipped to [min, max]."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exp_quantiles(n: int, mean: float) -> np.ndarray:
    return np.array([-mean * math.log(1.0 - (i + 0.5) / n)
                     for i in range(n)])


def counts(n: int, weights) -> np.ndarray:
    """``n`` split over ``weights`` by largest remainder."""
    w = np.asarray(weights, float)
    raw = n * w / w.sum()
    cnt = np.floor(raw).astype(np.int64)
    for i in np.argsort(-(raw - cnt), kind="stable")[: n - cnt.sum()]:
        cnt[i] += 1
    return cnt


def zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """How many of ``n`` sessions go to each of ``k`` prefix groups under
    Zipf popularity with exponent ``s``."""
    return counts(n, 1.0 / np.arange(1, k + 1) ** s)


def warp(times: np.ndarray, phases: list) -> np.ndarray:
    """Map arrival times of a steady process onto one whose rate follows
    ``phases`` (``[[seconds, multiplier], ...]``, repeated), with the same
    mean rate: the inverse of the cumulative intensity."""
    dur = np.array([p[0] for p in phases], float)
    mult = np.array([p[1] for p in phases], float)
    mult = mult * dur.sum() / (dur * mult).sum()
    edges = np.concatenate([[0.0], np.cumsum(dur * mult)])   # intensity
    period, span = dur.sum(), edges[-1]
    out = []
    for t in times:
        k, rem = divmod(t, span)
        i = min(int(np.searchsorted(edges, rem, side="right")) - 1,
                len(dur) - 1)
        out.append(k * period + dur[:i].sum() + (rem - edges[i]) / mult[i])
    return np.array(out)


class Traffic:
    """The requests of one run of a mix, drawn from the seed.

    ``initial`` are the requests known before the window (due in seconds
    from its opening; closed-loop first requests are due at 0 and are
    admitted during set-up).  ``follow`` gives the request a finished one
    leads to: the session's next turn, or a closed-loop client's next
    session; its due time is relative to the finish.
    """

    def __init__(self, mix: dict, seed: int, vocab: int, seconds: float):
        self.mix, self.vocab = mix, vocab
        self.rng = np.random.default_rng(seed)
        self.sch = sch = np.random.default_rng(SCHEDULE)
        self.arrival = arr = mix["arrival"]
        self.closed = arr["kind"] == "closed"
        self.preroll_s = 0.0 if self.closed else float(arr["preroll_s"])
        if self.closed:
            n = arr["clients"] * CLOSED_POOL
        else:
            n = max(1, round(arr["rate_per_s"]
                             * (self.preroll_s + seconds)))
        self.sessions = self._sessions(n)
        self.next_rid = self.next_session = 0
        self.initial: list[Req] = []
        if self.closed:
            age = sch.permutation(
                (np.arange(arr["clients"]) + 0.5) / arr["clients"])
            for c in range(arr["clients"]):
                r = self._start(c, 0.0)
                if arr["resume"]:
                    done = int(age[c] * r.max_new)
                    r.prompt += self._tokens(done)
                    r.max_new -= done
                self.initial.append(r)
        else:
            gaps = sch.permutation(exp_quantiles(n, 1.0 / arr["rate_per_s"]))
            due = warp(np.cumsum(gaps), arr["phases"])
            self.initial = [self._start(-1, float(t) - self.preroll_s)
                            for t in due]

    def _tokens(self, n: int) -> list[int]:
        return self.rng.integers(1, self.vocab, size=n).tolist()

    def _sessions(self, n: int) -> list[_Session]:
        """``n`` sessions: their classes, prefix groups and lengths, the
        same for every seed."""
        classes, sch = self.mix["classes"], self.sch
        cls_of = sch.permutation(np.repeat(
            np.arange(len(classes)), counts(n, [c["share"] for c in classes])))
        out: list[_Session | None] = [None] * n
        for ci, c in enumerate(classes):
            idx = np.flatnonzero(cls_of == ci)
            if not idx.size:
                continue
            t = c["turns"]
            plen = sch.permutation(
                lognormal_quantiles(idx.size * t, c["prompt_tokens"]))
            olen = sch.permutation(
                lognormal_quantiles(idx.size * t, c["output_tokens"]))
            pre = c["prefix"]
            heads = [self._tokens(pre["tokens"]) for _ in range(pre["groups"])]
            group = (sch.permutation(np.repeat(
                np.arange(pre["groups"]),
                zipf_counts(idx.size, pre["groups"], pre["zipf_s"])))
                if pre["groups"] else None)
            for j, i in enumerate(idx):
                out[i] = _Session(
                    cls=c, head=heads[group[j]] if pre["groups"] else [],
                    prompt_len=[int(x) for x in plen[j * t:(j + 1) * t]],
                    out_len=[int(x) for x in olen[j * t:(j + 1) * t]])
        return out

    def _req(self, prompt, max_new, due, session, turn, client) -> Req:
        r = Req(rid=self.next_rid, prompt=prompt, max_new=max_new, due=due,
                session=session, turn=turn, client=client)
        self.next_rid += 1
        return r

    def _start(self, client: int, due: float) -> Req:
        """The first turn of the next session in the schedule."""
        k = self.next_session
        self.next_session += 1
        s = self.sessions[k % len(self.sessions)]
        return self._req(s.head + self._tokens(s.prompt_len[0]),
                         s.out_len[0], due, k, 0, client)

    def follow(self, r: Req) -> tuple[Req, float] | None:
        """The request that ``r``'s finish leads to, and its delay."""
        s = self.sessions[r.session % len(self.sessions)]
        if r.turn + 1 < len(s.out_len):
            t = r.turn + 1
            return (self._req(r.prompt + r.out
                              + self._tokens(s.prompt_len[t]),
                              s.out_len[t], 0.0, r.session, t, r.client),
                    s.cls["think_s"])
        if self.closed:
            return self._start(r.client, 0.0), s.cls["think_s"]
        return None


def max_context(mix: dict) -> int:
    """Longest prompt plus output any request of the mix can reach."""
    return max(c["prefix"]["tokens"] + c["turns"]
               * (c["prompt_tokens"]["max"] + c["output_tokens"]["max"])
               for c in mix["classes"])


def fresh_prompt_range(mix: dict) -> tuple[int, int]:
    """Shortest and longest prompt a request made inside the window can
    have (closed-loop first sessions, resumed or not, are admitted during
    set-up)."""
    lo = min(c["prefix"]["tokens"] + c["prompt_tokens"]["min"]
             for c in mix["classes"])
    hi = max(c["prefix"]["tokens"] + c["prompt_tokens"]["max"]
             + (c["turns"] - 1) * (c["prompt_tokens"]["max"]
                                   + c["output_tokens"]["max"])
             for c in mix["classes"])
    return lo, hi


def max_hit_tokens(mix: dict) -> int:
    """Longest prompt prefix a request of the mix can find cached: a
    shared prefix, or in a session of several turns the whole prompt of
    its turn before last (0 for none)."""
    return max(max(c["prefix"]["tokens"] if c["prefix"]["groups"] else 0,
                   0 if c["turns"] < 2 else c["prefix"]["tokens"]
                   + (c["turns"] - 1) * c["prompt_tokens"]["max"]
                   + (c["turns"] - 2) * c["output_tokens"]["max"])
               for c in mix["classes"])
