"""Elastic scaling: rebuild mesh + reshard state when the device fleet
changes.  Partitioning makes this first-class: losing a pod = dropping one
partition (PartitionRuntime.drop_partition); losing chips *within* a pod
requires a remesh + reshard, implemented here.

Recovery flow on failure:
  1. ``plan_mesh(n_devices)``: largest (data, model) grid the survivors
     support (model axis preserved if possible — param specs stay valid);
  2. restore the last checkpoint with shardings for the new mesh
     (CheckpointManager.restore(..., shardings=...));
  3. batch divisibility re-checked via mesh.batch_axes; global batch is
     kept by raising grad-accumulation (accum' = accum * old/new).
"""
from __future__ import annotations

import jax

from repro.launch import sharding as SH


def plan_mesh(n_devices: int, model_axis: int = 16, prefer_model: bool = True):
    """Largest usable (data, model) factorization of the surviving fleet."""
    if n_devices < 1:
        raise ValueError(f"cannot mesh {n_devices} devices")
    m = model_axis
    while prefer_model and m > 1 and n_devices % m:
        m //= 2
    data = n_devices // m
    if data < 1:
        raise ValueError(f"cannot mesh {n_devices} devices")
    usable = data * m
    return (data, m), usable


def remesh_state(state, cfg, old_mesh, new_mesh):
    """Re-place a (params/opt) pytree from old_mesh shardings to new_mesh.

    On a real fleet this is a resharding transfer (device_put handles the
    all-to-all); semantics identical here."""
    new_shard = SH.param_shardings(jax.eval_shape(lambda: state), cfg,
                                   new_mesh)
    return jax.tree.map(lambda x, s: jax.device_put(x, s), state, new_shard)


def accum_for_batch(global_batch: int, old_devices: int, new_devices: int,
                    accum: int) -> int:
    """Keep the global batch when the fleet shrinks: scale microbatching."""
    scale = max(1, round(old_devices / max(new_devices, 1)))
    return accum * scale
