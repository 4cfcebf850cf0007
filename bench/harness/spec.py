"""Find a cell and what it names by file name: the cell under ``cells/``,
its model configuration under ``configs/`` and its traffic mix under
``traffic/``.  Adding a cell means adding files; nothing here changes.
"""

from __future__ import annotations

import json
import pathlib
from dataclasses import dataclass

BENCH = pathlib.Path(__file__).resolve().parents[1]


@dataclass(frozen=True)
class Cell:
    name: str
    cell: dict           # bench/cells/<name>.json
    config: dict         # bench/configs/<cell["config"]>.json
    mix: dict            # bench/traffic/<cell["traffic"]>.json


def _load(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file not found: {path}")
    return json.loads(path.read_text())


def load_cell(name: str, root: pathlib.Path = BENCH) -> Cell:
    cell = _load(root / "cells" / f"{name}.json")
    return Cell(name=name, cell=cell,
                config=_load(root / "configs" / f"{cell['config']}.json"),
                mix=_load(root / "traffic" / f"{cell['traffic']}.json"))


def model_dims(config: dict) -> dict:
    """The sizes the model is run at, from the configuration file's own
    (Hugging Face) keys."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    return {"n_layers": config["num_hidden_layers"], "d_model": d,
            "n_heads": h, "n_kv_heads": config["num_key_value_heads"],
            "head_dim": config.get("head_dim", d // h),
            "d_ff": config["intermediate_size"],
            "vocab": config["vocab_size"],
            "qkv_bias": bool(config.get("attention_bias", False)),
            "rope_theta": float(config["rope_theta"]),
            "norm_eps": float(config["rms_norm_eps"])}
