"""Logical-axis sharding rules (DESIGN.md §6).

Model / serving / training code never mentions mesh axes directly; every
tensor is annotated with *logical* axis names (``act_batch``, ``act_mlp``,
``embed``, ``expert``, ...) and a ``Rules`` object resolves those names to
mesh axes (or ``None`` = replicated) per phase:

* ``resolve_rules(mesh, cfg, phase)`` builds the table for a phase in
  {"train", "prefill", "decode", "long_decode"} — batch data-parallel over
  ``data`` (+ ``pod`` when present), tensor-parallel over ``model`` for
  heads / mlp / experts / vocab, FSDP-style parameter sharding in train.
* ``rules.shard(x, *logical)`` applies a ``with_sharding_constraint``;
  unknown / ``None`` names mean replicated, and any logical axis whose mesh
  extent does not divide the tensor dimension falls back to replicated so
  the same annotations run on a 1x1 host mesh and a 16x16 pod.
* ``param_shardings(rules, logical_specs)`` maps a pytree of logical-axis
  tuples (``models.model.param_logical_specs``) to ``NamedSharding``s for
  ``jax.jit`` in/out shardings.

Per-arch overrides come from ``configs.sharding_overrides(arch, mode)``
({logical: mesh_axes}) and are merged last.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# mesh axis aliases
_DATA = "data"
_MODEL = "model"
_POD = "pod"

# axis name of the 1-D point-sharding mesh used by the distributed
# geometric partitioner (repro.partition.distributed)
PARTITION_AXIS = "shard"

# axis names of the 2-D hierarchical-partitioner mesh: the coarse k1-way
# cut shards its points over the *product* of both axes (so it is
# bit-identical to the flat 1-D run over P1*P2 devices — a psum over
# ("coarse", "refine") reduces in the same flattened device order), and
# the k1 refinement blocks then batch over REFINE_AXIS alone
COARSE_AXIS = "coarse"
REFINE_AXIS = "refine"


def partition_mesh(devices: int | None = None,
                   axis_name: str = PARTITION_AXIS) -> Mesh:
    """1-D device mesh for the sharded partitioner: points/weights live on
    ``axis_name``, centers/influence are replicated.

    ``devices=None`` spans every visible device; an int takes the first
    ``devices`` of ``jax.devices()``. CPU hosts grow virtual devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set before the
    first jax import — tests/conftest.py and the CI workflow both do).
    """
    avail = jax.devices()
    n = len(avail) if devices is None else int(devices)
    if not 1 <= n <= len(avail):
        raise ValueError(
            f"devices={devices} out of range: {len(avail)} visible jax "
            f"device(s); on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={devices} before the "
            f"first jax import")
    return Mesh(np.asarray(avail[:n]), (axis_name,))


def partition_mesh2d(p1: int, p2: int) -> Mesh:
    """2-D ``(COARSE_AXIS, REFINE_AXIS)`` device mesh for the hierarchical
    sharded partitioner: the first ``p1 * p2`` visible devices reshaped to
    ``[p1, p2]``, row-major.

    The flattened device order equals ``partition_mesh(p1 * p2)``'s, which
    is what makes the coarse pass (sharded over the axis *product*)
    bit-identical to the flat 1-D run — same partial-sum placement, same
    psum reduction order.
    """
    p1, p2 = int(p1), int(p2)
    if p1 < 1 or p2 < 1:
        raise ValueError(f"mesh extents must be >= 1, got ({p1}, {p2})")
    avail = jax.devices()
    if p1 * p2 > len(avail):
        raise ValueError(
            f"devices=({p1}, {p2}) needs {p1 * p2} devices but only "
            f"{len(avail)} are visible; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={p1 * p2} before the "
            f"first jax import")
    return Mesh(np.asarray(avail[:p1 * p2]).reshape(p1, p2),
                (COARSE_AXIS, REFINE_AXIS))


def shard_map(f, *, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` as every SPMD body of this repository uses it.

    Replication checking is off (``check_vma=False``, the current name of
    the former ``check_rep=False``): centers, influence and metric totals
    are replicated by construction (they are psum results, or identical
    inputs), which the checker cannot always prove through the k-means
    while-loops and the assign kernels.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _batch_axes(mesh: Mesh):
    if _POD in mesh.axis_names:
        return (_POD, _DATA)
    return _DATA


def _default_table(mesh: Mesh, phase: str) -> dict:
    batch = _batch_axes(mesh)
    table: dict[str, Any] = {
        # --- activations
        "act_batch": batch,
        "act_seq": None,            # flash path q-chunks when seq unsharded
        "act_res_seq": None,        # residual-stream sequence axis
        "logits_seq": None,
        "act_embed": None,
        "act_mlp": _MODEL,
        "act_heads": _MODEL,
        "act_kv": _MODEL,
        "act_vocab": _MODEL,
        "act_e_embed": None,
        # --- caches
        "cache_seq": None,
        "cache_kv": _MODEL,
        # --- params
        "repeat": None,             # stacked-layer leading axis
        "nil": None,
        "embed": _DATA if phase == "train" else None,   # FSDP in train
        "mlp": _MODEL,
        "heads": _MODEL,
        "heads_joined": _MODEL,
        "kv_heads": _MODEL,
        "head_dim": None,
        "vocab": _MODEL,
        "rank": None,
        "state": None,
        "conv": None,
        "expert": _MODEL,
        "e_embed": None,
        "e_mlp": None,
        "codebooks": None,
    }
    return table


def _axis_extent(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    ext = 1
    for a in axes:
        ext *= mesh.shape[a]
    return ext


@dataclasses.dataclass(frozen=True)
class Rules:
    """Resolved logical->mesh table for one (mesh, config, phase)."""
    mesh: Mesh
    table: Mapping[str, Any]
    phase: str = "train"

    def spec(self, *logical) -> P:
        """PartitionSpec for a tuple of logical axis names (None entries and
        unknown names are replicated)."""
        return P(*[self.table.get(name) if name is not None else None
                   for name in logical])

    def sharding(self, logical) -> NamedSharding:
        """NamedSharding for a logical-axis tuple (e.g. a param spec)."""
        return NamedSharding(self.mesh, self.spec(*logical))

    def shard(self, x, *logical):
        """Constrain ``x`` to the resolved sharding. Logical names must
        match ``x.ndim``; axes whose mesh extent does not divide the
        corresponding dimension are dropped (replicated) so the same code
        runs on any mesh."""
        names = list(logical)
        assert len(names) == x.ndim, (
            f"{len(names)} logical names for rank-{x.ndim} tensor")
        resolved = []
        for dim, name in zip(x.shape, names):
            axes = self.table.get(name) if name is not None else None
            ext = _axis_extent(self.mesh, axes)
            resolved.append(axes if ext > 1 and dim % ext == 0 else None)
        if all(r is None for r in resolved):
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*resolved)))


def resolve_rules(mesh: Mesh, cfg, phase: str, batch_size: int | None = None,
                  overrides: Mapping[str, Any] | None = None) -> Rules:
    """Build the sharding rules for ``phase``.

    ``batch_size``: when given and not divisible by the batch-axis extent,
    batch data-parallelism is dropped (replicated batch) instead of failing
    at trace time. ``overrides``: {logical: mesh_axes} merged last (per-arch
    ``SHARDING_OVERRIDES`` from the config registry).
    """
    if phase not in ("train", "prefill", "decode", "long_decode"):
        raise ValueError(f"unknown phase {phase!r}")
    table = _default_table(mesh, phase)
    if batch_size is not None:
        ext = _axis_extent(mesh, table["act_batch"])
        if ext > 1 and batch_size % ext != 0:
            table["act_batch"] = None
    if overrides:
        table.update(overrides)
    # drop mesh axes the mesh does not have (e.g. "pod" overrides on a
    # single-pod mesh)
    names = set(mesh.axis_names)

    def known(axes):
        if axes is None:
            return None
        if isinstance(axes, str):
            return axes if axes in names else None
        kept = tuple(a for a in axes if a in names)
        return kept if kept else None

    table = {k: known(v) for k, v in table.items()}
    return Rules(mesh=mesh, table=table, phase=phase)


def param_shardings(rules: Rules, logical_specs):
    """Pytree of logical-axis tuples -> pytree of NamedShardings."""
    return jax.tree.map(rules.sharding, logical_specs,
                        is_leaf=lambda x: isinstance(x, tuple))
