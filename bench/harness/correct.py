"""What decides ``correct``: the served tokens against the plain reference.

After the window, a seeded sample of the requests that served tokens (the
one with the most among them) is run through the float32 reference over
its prompt and served tokens.  Each served token is greedy, so its
reference logit should be the reference's best; the number compared is
the widest gap by which a served token's logit lies below the best.  The
control puts the same reference, computed in float8, in the program's
place and reads the gap of the token it ranks first.
"""

from __future__ import annotations

import numpy as np

from . import reference

_ROW_BLOCK = 64


def sample(reqs, seed: int, tokens: int) -> list:
    """The request with the most served tokens, then others in seeded
    order, until ``tokens`` served tokens are covered."""
    served = sorted((r for r in reqs if r.out), key=lambda r: -len(r.out))
    if not served:
        return []
    rest = served[1:]
    order = np.random.default_rng(seed ^ 0x5EED).permutation(len(rest))
    out, n = [served[0]], len(served[0].out)
    for i in order:
        if n >= tokens:
            break
        out.append(rest[i])
        n += len(rest[i].out)
    return out


def _inputs(reqs):
    seqs, rows = [], []
    for r in reqs:
        seqs.append(r.prompt + r.out[:-1])
        rw = [len(r.prompt) - 1 + j for j in range(len(r.out))]
        pad = -len(rw) % _ROW_BLOCK
        rows.append(rw + [rw[-1]] * pad)
    return seqs, rows


def served_gaps(dims: dict, seed: int, reqs) -> np.ndarray:
    """Reference best logit minus the reference logit of each served
    token, over every served token of ``reqs``."""
    seqs, rows = _inputs(reqs)
    out = []
    for r, lg in zip(reqs, reference.logits_at(dims, seed, seqs, rows)):
        lg = lg[:len(r.out)]
        out.append(lg.max(-1) - lg[np.arange(len(r.out)), r.out])
    return np.concatenate(out)


def control_gaps(dims: dict, seed: int, reqs) -> np.ndarray:
    """The same gap for the token the float8 reference ranks first, at
    each position of the same prompts and served tokens."""
    seqs, rows = _inputs(reqs)
    ref = reference.logits_at(dims, seed, seqs, rows)
    low = reference.logits_at(dims, seed, seqs, rows, precision="fp8")
    out = []
    for r, a, b in zip(reqs, ref, low):
        a, b = a[:len(r.out)], b[:len(r.out)]
        out.append(a.max(-1) - a[np.arange(len(r.out)), b.argmax(-1)])
    return np.concatenate(out)
