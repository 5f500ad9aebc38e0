"""repro.runtime.elastic: remesh planning + global-batch preservation.

``PartitionRuntime`` re-derives its grad-accumulation factor through
``accum_for_batch`` on every membership change — absolute from the initial
fleet, so drop-then-replace lands back exactly at the original accum.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core.partitioning import PartitionConfig
from repro.runtime.elastic import accum_for_batch, plan_mesh
from repro.runtime.partition_runtime import PartitionRuntime

# ---------------------------------------------------------------------------
# mesh planning
# ---------------------------------------------------------------------------


def test_plan_mesh_prefers_model_axis():
    assert plan_mesh(16) == ((1, 16), 16)
    assert plan_mesh(64) == ((4, 16), 64)
    # 24 devices can't keep m=16; halving finds m=8
    assert plan_mesh(24) == ((3, 8), 24)
    assert plan_mesh(1) == ((1, 1), 1)
    # a prime fleet degrades all the way to pure data parallelism
    assert plan_mesh(7) == ((7, 1), 7)
    with pytest.raises(ValueError, match="cannot mesh"):
        plan_mesh(0)


# ---------------------------------------------------------------------------
# global-batch preservation
# ---------------------------------------------------------------------------


def test_accum_for_batch_scales_with_shrink():
    assert accum_for_batch(256, 16, 16, 2) == 2   # no change
    assert accum_for_batch(256, 16, 8, 2) == 4    # halved fleet: 2x accum
    assert accum_for_batch(256, 16, 4, 2) == 8
    assert accum_for_batch(256, 16, 5, 2) == 6    # round(16/5)=3
    assert accum_for_batch(256, 16, 0, 2) == 32   # degenerate: clamps


def _tiny_runtime(partitions):
    class _Api:
        def init(self, key):
            return {"w": jnp.zeros((2,), jnp.float32)}

    def step(params, opt, batch):
        return params, opt, {"loss": jnp.float32(0.0)}

    pc = PartitionConfig(partitions=partitions, sync_every=2)
    return PartitionRuntime(_Api(), step, pc, jax.random.PRNGKey(0),
                            accum=2, global_batch=64)


def test_runtime_rescales_accum_absolutely():
    """drop -> accum doubles; replacement join -> back to the original
    (absolute re-derivation from the initial fleet, not incremental)."""
    rt = _tiny_runtime(4)
    assert rt.accum == 2
    rt.drop_partition(3)
    rt.drop_partition(2)
    assert len(rt.alive_parts()) == 2
    assert rt.accum == 4          # half the fleet: global batch preserved
    rt.add_partition(2)
    assert rt.accum == 2          # round(4/3)=1: back at accum0
    rt.add_partition(3)
    assert len(rt.alive_parts()) == 4
    assert rt.accum == 2          # full fleet: exactly the original
