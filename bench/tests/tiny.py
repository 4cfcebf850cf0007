"""A tiny cell on the CPU: the harness driven end to end without the
chip look, for the tests.

Its limit on the logit gap, 0.08, lies between the readings it was set
from on the CPU, seeds 20-33 of both cells: sound runs read 0.0-0.0565,
the float8 control 0.122-0.468 (0.328-0.468 on ``tiny-reason``)."""

import json
import pathlib
import shutil
import time

import jax

from bench.harness import runner, spec

DATA = pathlib.Path(__file__).resolve().parent / "data" / "tiny"
BENCH = {"end_to_end": [
    {"name": "ttft_p50_ms", "unit": "ms", "workloads": ["tiny-chat"]},
    {"name": "tbt_p95_ms", "unit": "ms"},
    {"name": "output_tok_s", "unit": "tokens/s"},
    {"name": "setup_s", "unit": "s"}], "per_layer": []}
CELLS = {
    "tiny-chat": {
        "config": "tiny", "traffic": "chat", "chips": 1, "codec": "bdi",
        "prefix_cache": True,
        "engine": {"page_size": 16, "max_batch": 4, "prefill_chunk": 32,
                   "token_budget": 132, "n_pool_pages": 2000},
        "check": {"sample_tokens": 10000, "logit_gap_limit": 0.08}},
    "tiny-reason": {
        "config": "tiny", "traffic": "reason", "chips": 1, "codec": "bdi",
        "prefix_cache": True,
        "engine": {"page_size": 16, "max_batch": 4, "prefill_chunk": 32,
                   "token_budget": 132, "n_pool_pages": 2000},
        "check": {"sample_tokens": 10000, "logit_gap_limit": 0.08}}}


def root(tmp_path: pathlib.Path, *names: str) -> pathlib.Path:
    """A benchmark tree holding the tiny config and mixes, with the named
    cells added as files."""
    r = tmp_path / "bench"
    shutil.copytree(DATA, r)
    (r / "cells").mkdir()
    for n in names:
        (r / "cells" / f"{n}.json").write_text(json.dumps(CELLS[n]))
    return r


def run(tmp_path, name: str, seed: int, seconds: float = 2.0,
        control: bool = False) -> dict:
    cell = spec.load_cell(name, root(tmp_path, name))
    return runner.run_cell(cell, seed, seconds, trace_on=False,
                           t_process=time.perf_counter(), bench=BENCH,
                           devs=jax.devices(), log=lambda s: None,
                           control=control)
