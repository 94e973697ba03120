"""shard_map construction + backend gating.

Two distinct shard_map shapes live in this repo, and they have very
different backend support:

- FULLY-MANUAL (``shard_map_manual``): every mesh axis is manual; the
  body sees per-shard local shapes and the partitioner never has to mix
  manual and automatic subgroups.  This lowers on EVERY backend,
  including the CPU partitioner — it is what the slot-sharded continuous
  serving engine uses (``serving.sharded``), which is why the sharded
  serving oracle can run under ``--xla_force_host_platform_device_count``.

- PARTIAL-AUTO (``shard_map_partial_auto``): manual over a subset of
  axes (the gradient wire's 'pod' hop), the rest left to GSPMD.  On CPU
  builds the SPMD partitioner hard-ABORTS (CHECK
  ``target.IsManualSubgroup() == sharding().IsManualSubgroup()``, not a
  catchable exception) on ANY partial-auto shard_map — measured in the
  ISSUE-2 multipod A/B, DESIGN.md §5 — so callers must gate on
  ``SHARD_MAP_WIRE_BACKENDS`` before tracing one.

Both helpers build ``jax.shard_map``, which takes the *manual* axis set
via ``axis_names``.
"""
from __future__ import annotations

from typing import FrozenSet

import jax

# Backends where tracing a PARTIAL-AUTO shard_map is safe.  CPU is out
# (partitioner CHECK-abort, see module docstring); real pods are TPU and
# the first TPU run should validate the packed pod wire (ROADMAP).
SHARD_MAP_WIRE_BACKENDS = ("tpu",)


def partial_auto_ok() -> bool:
    """Is a partial-auto shard_map safe to *trace* on this backend?"""
    return jax.default_backend() in SHARD_MAP_WIRE_BACKENDS


def shard_map_manual(body, mesh, in_specs, out_specs):
    """Fully-manual shard_map: manual over EVERY axis of ``mesh``.

    The body sees local (per-shard) shapes for every input whose spec
    names a mesh axis; replication checking is disabled (serving bodies
    return owner-masked values that are replicated by construction).
    Safe on all backends — no manual/auto subgroup mixing exists for the
    partitioner to choke on.
    """
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=set(mesh.axis_names), check_vma=False)


def shard_map_partial_auto(body, mesh, in_specs, out_specs,
                           manual_axes: FrozenSet[str] = frozenset({"pod"})):
    """Partial-manual shard_map: manual over ``manual_axes``, rest auto.

    The gradient-wire shape (manual 'pod' hop, 'data'/'model' left to
    GSPMD).  Callers MUST gate on ``partial_auto_ok()`` — the CPU
    partitioner hard-aborts (uncatchable CHECK) on partial-auto.
    """
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=set(manual_axes),
                         check_vma=False)


def mesh_fingerprint(mesh):
    """Hashable identity of a mesh for compile-cache keys (None -> None).

    Two meshes compile to different executables whenever their axis
    layout OR their device assignment differs, so both go into the key —
    ``serving.engine.cached_program`` entries built for one mesh must
    never be handed to an engine on another (or to an unsharded engine,
    which keys with None).
    """
    if mesh is None:
        return None
    return (tuple(mesh.axis_names),
            tuple(int(mesh.shape[a]) for a in mesh.axis_names),
            tuple(int(d.id) for d in mesh.devices.flat))
