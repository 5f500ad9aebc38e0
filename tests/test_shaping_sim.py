"""Paper-reproduction gates + hypothesis property tests for the simulator."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.shaping_sim import (Task, maxmin_fair, partition_sweep,
                                    simulate, tasks_from_traces)
from repro.models.cnn import LayerTrace, model_traces


# ---------------------------------------------------------------------------
# max-min fairness properties
# ---------------------------------------------------------------------------


@given(st.lists(st.floats(0, 1e12), min_size=1, max_size=8),
       st.floats(1e3, 1e12))
@settings(max_examples=200, deadline=None)
def test_maxmin_fair_properties(demands, cap):
    d = np.asarray(demands)
    a = maxmin_fair(d, cap)
    assert (a <= d + 1e-6).all()                    # never over-allocate
    assert a.sum() <= cap * (1 + 1e-9)              # respect capacity
    if d.sum() <= cap:                              # no contention: all granted
        np.testing.assert_allclose(a, d, rtol=1e-6, atol=1e-3)
    else:
        assert a.sum() >= cap * (1 - 1e-6)          # work-conserving


# ---------------------------------------------------------------------------
# simulator conservation / sanity
# ---------------------------------------------------------------------------


def test_throughput_bounded_by_compute_and_bandwidth():
    tr = model_traces("resnet50")
    r = simulate(tr, partitions=1, total_batch=64, n_passes=4, stagger="none")
    tasks = tasks_from_traces(tr, 64, 64)
    ideal = sum(t.dur for t in tasks)
    bw_bound = sum(t.byts for t in tasks) / 400e9
    max_rate = 64 / max(ideal, bw_bound)
    assert r.throughput <= max_rate * 1.02
    assert r.throughput > 0


@pytest.mark.parametrize("model", ["resnet50", "googlenet"])
def test_paper_reproduction_gates(model):
    """Fig.5 gates: perf up, std down, avg up; ResNet/GoogleNet in band."""
    tr = model_traces(model)
    rows = partition_sweep(tr, [2, 4, 8, 16], total_batch=64, n_passes=6)
    base = rows[1]
    best = max(rows, key=lambda p: rows[p]["perf"])
    perf = rows[best]["perf"] - 1
    assert 0.03 < perf < 0.25, f"{model}: {perf}"
    assert rows[best]["bw_std"] < base["bw_std"]
    assert rows[best]["bw_mean"] > base["bw_mean"]
    # monotone-ish improvement with P (paper: steady improvement)
    assert rows[16]["perf"] >= rows[2]["perf"]


def test_vgg_gains_small_but_positive():
    tr = model_traces("vgg16")
    rows = partition_sweep(tr, [2, 4, 8], total_batch=64, n_passes=6)
    best = max(rows[p]["perf"] for p in (2, 4, 8))
    assert 0.0 < best - 1 < 0.10  # paper: +3.9%, smallest of the three


@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=5, deadline=None)
def test_random_stagger_still_shapes(seed):
    tr = model_traces("googlenet")
    base = simulate(tr, partitions=1, total_batch=64, n_passes=4,
                    stagger="none")
    r = simulate(tr, partitions=8, total_batch=64, n_passes=4,
                 stagger="random", seed=seed)
    assert r.bw_std < base.bw_std  # shaping holds for any phase draw


def test_conservation_of_bytes():
    """Total bytes moved is invariant to partitioning (modulo weight
    replication, which must equal (P-1) x weight bytes)."""
    tr = model_traces("resnet50")
    t1 = tasks_from_traces(tr, 64, 64)
    t4 = tasks_from_traces(tr, 16, 16)
    w = sum(t.weight_bytes for t in tr)
    b1 = sum(t.byts for t in t1)
    b4 = 4 * sum(t.byts for t in t4)
    np.testing.assert_allclose(b4 - b1, 3 * w, rtol=1e-6)
