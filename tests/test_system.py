"""End-to-end system tests: training convergence, fault tolerance,
serving, and the dry-run machinery."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.launch.train import train

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_training_loss_decreases(tmp_path):
    out = train("yi-6b", smoke=True, steps=40, seq_len=64, batch=8,
                lr=1e-3, ckpt_dir=None, log_every=100)
    assert out["final_loss"] < out["first_loss"] - 0.3


def test_checkpoint_restart_continuity(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    train("yi-6b", smoke=True, steps=20, seq_len=32, batch=4,
          ckpt_dir=ckpt, ckpt_every=10, log_every=100)
    # relaunch: must resume at 20 and continue to 30
    out2 = train("yi-6b", smoke=True, steps=30, seq_len=32, batch=4,
                 ckpt_dir=ckpt, ckpt_every=10, log_every=100)
    assert out2["steps_run"] == 10
    # uninterrupted reference run matches the restarted one
    ckpt2 = str(tmp_path / "ckpt2")
    ref = train("yi-6b", smoke=True, steps=30, seq_len=32, batch=4,
                ckpt_dir=ckpt2, ckpt_every=30, log_every=100)
    np.testing.assert_allclose(out2["final_loss"], ref["final_loss"],
                               rtol=1e-5)


def test_deadline_checkpoint(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    out = train("yi-6b", smoke=True, steps=10_000, seq_len=32, batch=4,
                ckpt_dir=ckpt, ckpt_every=10_000, deadline_s=5,
                log_every=100)
    assert out["steps_run"] < 10_000
    from repro.checkpoint import store
    assert store.latest_step(ckpt) == out["steps_run"]


def test_serve_generation_runs():
    from repro.launch.serve import generate
    out = generate("qwen2.5-14b", smoke=True, batch=2, prompt_len=8, gen=8)
    assert len(out["tokens"]) >= 1
    assert out["tok_per_s"] > 0


def test_serve_scheduler_full_depth_finishes():
    """Every published page takes one pool id per layer, so the pool that
    ``generate`` builds must hold a long prompt at yi-6b's full depth
    (reduced width) without CAMP-preempting it."""
    from repro.launch.serve import generate
    out = generate("yi-6b", smoke=True, n_layers=32, scheduler=True,
                   prefix_cache=True, batch=2, prompt_len=256, gen=4)
    assert out["n_layers"] == 32
    assert [r["reason"] for r in out["per_request"].values()] \
        == ["length", "length"]


def test_mini_dryrun_subprocess():
    """Full dry-run machinery for the cheapest cell, in a fresh process."""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = "/tmp/test_dryrun_cell.json"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "xlstm-350m",
         "--shape", "long_500k", "--out", out],
        capture_output=True, text=True, timeout=540, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    with open(out) as f:
        info = json.load(f)
    assert info["n_devices"] == 256
    assert info["hlo_flops"] > 0
    assert info["collectives"]["total"] >= 0
