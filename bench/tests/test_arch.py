"""Architectures and kernels are found by file.

The dense modules ``llama`` and ``qwen2`` reproduce what the harness's own
dense functions gave before they moved into ``bench/arch/``.  The weight
trees at tiny sizes and the operation counts at both cells' widths match
their digests (``data/arch_digests.json``) bit for bit.  The reference
logits, float32 and the float8 control, match frozen rows of them
(``data/arch_logits.npz``) to a millionth of their largest magnitude: XLA's
CPU backend sums in another order on another number of cores, which moves
float32 logits by up to 3.7e-7 of it.

A model type and a kernel that exist only as files under a test's own
root are picked up with no edit to the harness.
"""

import hashlib
import json
import pathlib

import jax
import numpy as np
import pytest

from bench.harness import runner, spec, trace
from bench.tests import tiny

DATA = pathlib.Path(__file__).resolve().parent / "data"
FROZEN = json.loads((DATA / "arch_digests.json").read_text())
LOGITS = np.load(DATA / "arch_logits.npz")
LOGIT_ATOL = 1e-6       # of the frozen rows' largest magnitude


def digest(tree) -> str:
    """Each leaf's path, type, shape and bytes, in tree order."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _weights_and_reference(model_type: str, what: str):
    want = FROZEN["archs"][model_type]
    arch = spec.load_arch(want["config"])
    d, seed = arch.dims_of(want["config"]), FROZEN["seed"]
    if what == "dims":
        return d, want["dims"]
    if what == "model":
        return digest(arch.model(d, seed)), want["model"]
    if what == "one_layer":
        return [digest(arch.one_layer(d, seed, li))
                for li in range(d["n_layers"])], want["one_layer"]
    if what == "outer_params":
        return digest(arch.outer_params(d, seed)), want["outer_params"]
    got = arch.logits_at(d, seed, FROZEN["seqs"], FROZEN["rows"],
                         precision=what)
    for i, lg in enumerate(got):
        ref = LOGITS[f"{model_type}.{what}.{i}"]
        np.testing.assert_allclose(
            np.asarray(lg)[LOGITS[f"pick.{i}"]], ref, rtol=0,
            atol=LOGIT_ATOL * np.abs(ref).max())
    return len(got), len(FROZEN["seqs"])


def _flops(config: str, what: str):
    want = FROZEN["flops"][config][what]
    c = json.loads((spec.BENCH / "configs" / f"{config}.json").read_text())
    arch = spec.load_arch(c)
    d = arch.dims_of(c)
    if what == "decode":
        return [[n, arch.decode_flops(n, d)] for n, _ in want], want
    return [[n, p, arch.prefill_flops(n, p, d)] for n, p, _ in want], want


@pytest.mark.parametrize("key,what", [
    *[(mt, w) for mt in ("llama", "qwen2")
      for w in ("dims", "model", "one_layer", "outer_params", "f32", "fp8")],
    *[(c, w) for c in ("yi-6b", "qwen2.5-14b-d12")
      for w in ("decode", "prefill")]])
def test_dense_modules_reproduce_the_frozen_digests(key, what):
    got, want = (_flops(key, what) if key in FROZEN["flops"]
                 else _weights_and_reference(key, what))
    assert got == want


# A model type of the test's own: the dense model, with its reference's
# logits raised by OFF at one token a position, the one after its best.
MODULE = '''
import numpy as np

from bench.arch import llama
from bench.arch.llama import (arch_config, decode_flops, dims_of, model,
                              prefill_flops)

OFF = {off}


def logits_at(*args, **kw):
    out = [np.array(lg) for lg in llama.logits_at(*args, **kw)]
    for lg in out if OFF else []:
        i = np.arange(len(lg))
        lg[i, (lg.argmax(-1) + 1) % lg.shape[-1]] = lg.max(-1) + OFF
    return out
'''


def test_a_model_type_added_as_a_file_runs_and_decides_correct(tmp_path):
    """The cell's own module builds the model and its reference decides
    ``correct``: a reference off by OFF at every position fails the run
    whose served tokens the sound one passes."""
    root = tiny.root(tmp_path)
    config = json.loads((root / "configs" / "tiny.json").read_text())
    out = {}
    for model_type, off in (("tiny_made", 0.0), ("tiny_made_off", 1.0)):
        assert not (spec.BENCH / "arch" / f"{model_type}.py").exists()
        (root / "arch" / f"{model_type}.py").write_text(
            MODULE.format(off=off))
        (root / "configs" / f"{model_type}.json").write_text(json.dumps(
            dict(config, model_type=model_type)))
        (root / "cells" / f"{model_type}.json").write_text(json.dumps(
            dict(tiny.CELLS["tiny-reason"], config=model_type)))
        cell = spec.load_cell(model_type, root)
        assert cell.arch.OFF == off
        out[off] = runner.run_cell(cell, 31, 2.0, trace_on=False,
                                   t_process=0.0, bench=tiny.BENCH,
                                   devs=jax.devices(), log=lambda s: None)
    sound, off = out[0.0], out[1.0]
    assert sound["correct"], sound
    assert not off["correct"]
    assert off["checks"]["logit_gap"]["value"] >= 1.0 - 1e-3
    assert off["checks"]["window_compiles"]["value"] == 0


def test_a_kernel_added_as_a_file_is_reduced(tmp_path):
    (tmp_path / "roofline").mkdir()
    (tmp_path / "roofline" / "norm_kernel.py").write_text(
        'KERNEL_OP = "add_rsqrt_fusion"\n')
    (tmp_path / "roofline" / "counts_only.py").write_text('"""No op."""\n')
    kernels = spec.kernel_ops(tmp_path)
    assert kernels == {"norm_kernel": "add_rsqrt_fusion"}
    r = trace.reduce(json.loads((DATA / "v5e_trace_rows.json").read_text()),
                     kernels)
    assert 0 < r["kernel_s"]["norm_kernel"] < r["busy_s"]
    assert spec.kernel_ops()["paged_attention"] == "_paged_attention_tail"


@pytest.mark.parametrize("model_type", [None, "no_such_model"])
def test_a_configuration_without_its_module_fails_at_load(tmp_path,
                                                          model_type):
    root = tiny.root(tmp_path, "tiny-chat")
    path = root / "configs" / "tiny.json"
    config = json.loads(path.read_text())
    del config["model_type"]
    if model_type:
        config["model_type"] = model_type
    path.write_text(json.dumps(config))
    with pytest.raises(FileNotFoundError) as err:
        spec.load_cell("tiny-chat", root)
    want = root / "arch" / f"{model_type or '<model_type>'}.py"
    assert str(want) in str(err.value)
