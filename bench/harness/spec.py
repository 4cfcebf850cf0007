"""Find a cell and what it names by file name: the cell under ``cells/``,
its model configuration under ``configs/``, its traffic mix under
``traffic/``, the architecture module ``arch/<model_type>.py`` that the
configuration's own ``model_type`` names, and the kernels of
``roofline/``.  Adding a cell, a configuration, an architecture or a
kernel means adding files; nothing here changes.  (``bench/rehearse.py``,
which sizes a cell's engine without a chip, knows dense KV pages only.)

An architecture module provides:

- ``dims_of(config)``: the sizes the model is run at (a dict of hashable
  values), from the configuration's keys;
- ``arch_config(name, dims)``: the program's ``ArchConfig``;
- ``model(dims, seed)``: the whole parameter tree in the program's
  layout, made on the device in one jitted call;
- ``logits_at(dims, seed, seqs, rows, precision="f32")``: the plain
  reference (``reference.py``), logits at positions ``rows[i]`` of each
  sequence ``seqs[i]``, its weights rebuilt from the seed alone;
  ``precision="fp8"`` is the control;
- ``decode_flops(ctx, dims)`` and ``prefill_flops(n, pos_sum, dims)``:
  the model operations of a token, for the whole step's share of the peak.

A kernel's ``roofline/<kernel>.py`` declares ``KERNEL_OP``, a substring of
its op name in a profiler trace; the trace's kernel times are keyed by
``<kernel>``.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
from dataclasses import dataclass
from types import ModuleType

BENCH = pathlib.Path(__file__).resolve().parents[1]


@dataclass(frozen=True)
class Cell:
    name: str
    cell: dict           # <root>/cells/<name>.json
    config: dict         # <root>/configs/<cell["config"]>.json
    mix: dict            # <root>/traffic/<cell["traffic"]>.json
    arch: ModuleType     # <root>/arch/<config["model_type"]>.py
    dims: dict           # arch.dims_of(config)
    root: pathlib.Path


def _load(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file not found: {path}")
    return json.loads(path.read_text())


def load_module(path: pathlib.Path, prefix: str) -> ModuleType:
    """The Python file ``path``, run as a module of its own."""
    spec = importlib.util.spec_from_file_location(f"{prefix}_{path.stem}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_arch(config: dict, root: pathlib.Path = BENCH) -> ModuleType:
    """The architecture module the configuration's ``model_type`` names."""
    mt = config.get("model_type")
    path = root / "arch" / f"{mt or '<model_type>'}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"architecture module not found: {path} (the configuration's "
            f"model_type is {mt!r})")
    return load_module(path, "bench_arch")


def load_cell(name: str, root: pathlib.Path = BENCH) -> Cell:
    cell = _load(root / "cells" / f"{name}.json")
    config = _load(root / "configs" / f"{cell['config']}.json")
    arch = load_arch(config, root)
    return Cell(name=name, cell=cell, config=config,
                mix=_load(root / "traffic" / f"{cell['traffic']}.json"),
                arch=arch, dims=arch.dims_of(config), root=root)


def kernel_ops(root: pathlib.Path = BENCH) -> dict[str, str]:
    """Each kernel's op name in a trace, keyed by the name of its
    ``roofline/<kernel>.py``: every module there that declares
    ``KERNEL_OP``."""
    out = {}
    for path in sorted((root / "roofline").glob("[!_]*.py")):
        op = getattr(load_module(path, "bench_roofline"), "KERNEL_OP", None)
        if op:
            out[path.stem] = op
    return out
