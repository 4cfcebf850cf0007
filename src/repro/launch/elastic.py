"""Elastic scaling: restore a checkpoint onto a different topology.

The checkpoint stores tensors logically (checkpoint/store.py), so a job
that trained on N devices can resume on M devices: build the new mesh,
re-derive sharding rules for it, and ``restore(..., target_shardings=...)``
— this module packages that flow plus a divisibility audit that reports
which parameters lose sharding on the new mesh (the capacity-planning
signal an operator needs before shrinking a fleet).

Usage (library):
    plan = reshard_plan(params_shape, old_mesh, new_mesh)
    params, _ = restore_elastic(ckpt_dir, params_shape, new_mesh)
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

from repro.checkpoint import store
from repro.distributed import sharding as SH


def reshard_plan(shape_tree, old_mesh: Mesh, new_mesh: Mesh) -> dict:
    """Audit how sharding changes between meshes.

    Returns {path: {"old": spec, "new": spec, "bytes": n,
                    "replicated_growth": factor}} for leaves whose
    per-device footprint grows on the new mesh.
    """
    old_specs = SH.param_specs(shape_tree, old_mesh)
    new_specs = SH.param_specs(shape_tree, new_mesh)
    flat, _ = jax.tree_util.tree_flatten_with_path(shape_tree)
    flat_old = jax.tree_util.tree_leaves(
        old_specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    flat_new = jax.tree_util.tree_leaves(
        new_specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))

    def shard_factor(spec, mesh):
        f = 1
        for s in spec:
            if s is None:
                continue
            for ax in (s if isinstance(s, tuple) else (s,)):
                f *= mesh.shape.get(ax, 1)
        return f

    report = {}
    for (key, leaf), so, sn in zip(flat, flat_old, flat_new):
        fo = shard_factor(so, old_mesh)
        fn = shard_factor(sn, new_mesh)
        if fn < fo:
            nbytes = int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            report[jax.tree_util.keystr(key)] = {
                "old": str(so), "new": str(sn), "bytes": nbytes,
                "replicated_growth": fo / fn,
            }
    return report


def restore_elastic(ckpt_dir: str, shape_tree, new_mesh: Mesh,
                    step: int | None = None):
    """Restore a checkpoint sharded for whatever mesh the new job has."""
    shardings = SH.param_shardings(shape_tree, new_mesh)
    with new_mesh:
        return store.restore(ckpt_dir, shape_tree, step=step,
                             target_shardings=shardings)
