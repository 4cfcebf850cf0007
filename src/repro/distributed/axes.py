"""Mesh context + activation-sharding helpers used throughout the models.

Models call ``shard(x, *axis_names)`` at layer boundaries; when a mesh is
active (set by the launcher via :func:`use_mesh`), this becomes a
``with_sharding_constraint`` with the corresponding ``PartitionSpec``; with
no mesh (CPU smoke tests) it is a no-op, so model code never branches.

Axis conventions (DESIGN.md §Distribution):
  * ``DP``    — data parallelism: ("pod", "data") when a pod axis exists,
                else ("data",). Batch/token dims shard here.
  * ``"model"`` — tensor/expert parallelism: attention heads, FFN hidden,
                vocab, experts.
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

DP = "__dp__"          # sentinel expanded to the mesh's data axes
MODEL = "model"


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` without replication checking, as the compressed
    collectives and the MoE path call it."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def current_mesh() -> Mesh | None:
    return getattr(_state, "mesh", None)


def dp_axes() -> tuple[str, ...]:
    mesh = current_mesh()
    if mesh is None:
        return ("data",)
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None):
    prev = current_mesh()
    _state.mesh = mesh
    try:
        if mesh is not None:
            with mesh:
                yield mesh
        else:
            yield None
    finally:
        _state.mesh = prev


def resolve(spec: tuple) -> P:
    """Expand the DP sentinel and drop axes absent from the current mesh."""
    mesh = current_mesh()
    names = set(mesh.axis_names) if mesh is not None else set()
    out: list = []
    for s in spec:
        if s == DP:
            axes = dp_axes()
            out.append(axes if len(axes) > 1 else (axes[0] if axes else None))
        elif s is None:
            out.append(None)
        elif isinstance(s, tuple):
            kept = tuple(a for a in s if a in names)
            out.append(kept if kept else None)
        else:
            out.append(s if s in names else None)
    return P(*out)


def shard(x: jax.Array, *spec) -> jax.Array:
    """Apply a sharding constraint if a mesh is active; no-op otherwise."""
    mesh = current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, resolve(spec)))


def named_sharding(*spec) -> NamedSharding:
    mesh = current_mesh()
    assert mesh is not None, "named_sharding requires an active mesh"
    return NamedSharding(mesh, resolve(spec))


SP_ENABLED = False   # sequence-parallel residual stream (hillclimb option)


def set_sp(enabled: bool) -> None:
    global SP_ENABLED
    SP_ENABLED = enabled


def shard_seq(x: jax.Array) -> jax.Array:
    """Megatron-style sequence parallelism for the residual stream.

    When enabled, shards [B, S, D] activations over ("dp", "model", None)
    so the per-layer remat checkpoints ([L, B, S, D]) shard over the full
    mesh instead of replicating across 'model'.  Off by default: the
    baseline bounds activation memory with microbatching instead (see
    launch/dryrun.py); SP is explored in the §Perf iteration log.
    """
    mesh = current_mesh()
    if mesh is None or x.ndim != 3:
        return x
    if not SP_ENABLED:
        return shard(x, DP, None, None)
    m = mesh.shape.get("model", 1)
    if m > 1 and x.shape[1] % m == 0:
        return shard(x, DP, MODEL, None)
    return shard(x, DP, None, None)
