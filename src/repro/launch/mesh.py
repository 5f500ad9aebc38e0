"""Production mesh construction.

Pure functions — importing this module never touches jax device state.

Mesh axes (outer -> inner):
  pod   : across pods (DCN; slow links).  Present when multi_pod.
  part  : traffic-shaping partitions *within* a pod (the paper's knob).
          Present when partitions > 1.
  data  : synchronous data parallel + FSDP weight storage within a partition.
  model : tensor/expert parallel (fast ICI dimension).

The paper's technique maps ``part`` (and, at deployment scale, ``pod``) to
asynchronous partition groups: weights are distinct per partition between
periodic syncs; batch shards across partitions; cross-partition collectives
happen only at sync points.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

POD_CHIPS = 256          # 16 x 16 v5e pod slice
DATA_AXIS = 16
MODEL_AXIS = 16
N_PODS = 2


def _mk(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, partitions: int = 1):
    """(16,16) data x model single-pod; (2,16,16) pod x data x model multi-pod.

    ``partitions`` > 1 factors the data axis into (part, data//part): cores in
    a partition stay synchronous, partitions run asynchronously (paper §3).
    """
    if partitions == 1:
        if multi_pod:
            return _mk((N_PODS, DATA_AXIS, MODEL_AXIS),
                       ("pod", "data", "model"))
        return _mk((DATA_AXIS, MODEL_AXIS), ("data", "model"))
    if DATA_AXIS % partitions:
        raise ValueError(f"partitions={partitions} must divide {DATA_AXIS}")
    inner = DATA_AXIS // partitions
    if multi_pod:
        return _mk((N_PODS, partitions, inner, MODEL_AXIS),
                   ("pod", "part", "data", "model"))
    return _mk((partitions, inner, MODEL_AXIS), ("part", "data", "model"))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the locally available devices (tests / examples)."""
    return _mk((data, model), ("data", "model"))


def batch_axes(mesh, global_batch: int):
    """Mesh axes the batch dim shards over, honouring divisibility.

    Prefers the widest sharding (pod, part, data); drops outer axes until the
    global batch divides the product (e.g. long_500k's batch of 1 replicates).
    """
    cand = [a for a in ("pod", "part", "data") if a in mesh.shape]
    while cand:
        n = 1
        for a in cand:
            n *= mesh.shape[a]
        if global_batch % n == 0:
            return tuple(cand)
        cand = cand[1:]
    return ()
