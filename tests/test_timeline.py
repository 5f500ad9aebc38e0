"""core.timeline: the shared contention clock, pinned against the
pre-refactor ``core.shaping_sim`` event loops.

Three layers of guarantees:
  * max-min fairness properties of the allocator (conservation, no
    over-allocation, binding-set fairness) — hypothesis property tests;
  * ContentionTimeline unit semantics (stretch under contention, timers,
    chained spans) against hand-computed fluid-model arithmetic;
  * refactor equivalence: ``simulate``/``simulate_tasks`` rebuilt on the
    timeline reproduce the exact pre-refactor bandwidth mean/std traces
    for the Fig. 5 sweep and the serving-trace report (values captured
    from the pre-refactor loops at tight tolerance).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.timeline import ContentionTimeline, Span, maxmin_fair


# ---------------------------------------------------------------------------
# max-min fairness properties
# ---------------------------------------------------------------------------


@given(st.lists(st.floats(0, 1e12), min_size=1, max_size=8),
       st.floats(1e3, 1e12))
@settings(max_examples=200, deadline=None)
def test_maxmin_conservation_and_demand_cap(demands, cap):
    d = np.asarray(demands)
    a = maxmin_fair(d, cap)
    assert (a <= d + 1e-6).all()            # never allocate above demand
    assert a.sum() <= cap * (1 + 1e-9)      # conservation: never above pipe
    if d.sum() <= cap:                      # no contention: all granted
        np.testing.assert_allclose(a, d, rtol=1e-6, atol=1e-3)
    else:
        assert a.sum() >= cap * (1 - 1e-6)  # work-conserving


@given(st.lists(st.floats(0, 1e12), min_size=2, max_size=8),
       st.floats(1e3, 1e12))
@settings(max_examples=200, deadline=None)
def test_maxmin_binding_set_fairness(demands, cap):
    """Fairness of the binding set: an unsatisfied flow's allocation is a
    maximum — no flow (satisfied or not) may receive more than any flow
    whose demand was cut."""
    d = np.asarray(demands)
    a = maxmin_fair(d, cap)
    tol = 1e-6 * max(cap, 1.0)
    unsat = a < d - tol
    if unsat.any():
        floor = a[unsat].min()
        assert (a <= floor + tol).all()
        # and the binding flows share equally among themselves
        np.testing.assert_allclose(a[unsat], floor, atol=tol)


# ---------------------------------------------------------------------------
# ContentionTimeline unit semantics
# ---------------------------------------------------------------------------


def test_single_span_uncontended_runs_at_full_speed():
    tl = ContentionTimeline(bandwidth=100.0)
    done = []
    tl.start(2.0, 50.0, on_complete=lambda sp, t: done.append(t))
    tl.run()
    assert done == [2.0]
    assert tl.bw_samples == [(0.0, 2.0, 25.0)]  # demand 25 < pipe: granted


def test_contention_stretches_the_over_demanding_span():
    """A (dur=1, bytes=200) span against a (dur=1, bytes=50) span on a
    100 B/s pipe: max-min gives each 50 B/s, so the heavy span runs at
    quarter speed until the light one finishes, then at half speed alone —
    completion at t=2.5 (hand-computed fluid model)."""
    tl = ContentionTimeline(bandwidth=100.0)
    ends = {}
    tl.start(1.0, 200.0, key="heavy",
             on_complete=lambda sp, t: ends.__setitem__("heavy", t))
    tl.start(1.0, 50.0, key="light",
             on_complete=lambda sp, t: ends.__setitem__("light", t))
    tl.run()
    assert ends["light"] == pytest.approx(1.0, rel=1e-12)
    assert ends["heavy"] == pytest.approx(2.5, rel=1e-12)
    # the pipe was saturated the whole time
    (t0, t1, bw0), (t2, t3, bw1) = tl.bw_samples
    assert (t0, t1) == (0.0, 1.0) and bw0 == pytest.approx(100.0)
    assert (t2, t3) == (1.0, 2.5) and bw1 == pytest.approx(100.0)


def test_timer_releases_work_and_orders_with_spans():
    tl = ContentionTimeline(bandwidth=100.0)
    events = []
    tl.start(1.0, 10.0, on_complete=lambda sp, t: events.append(("a", t)))
    tl.call_at(0.5, lambda t: (events.append(("timer", t)),
                               tl.start(1.0, 10.0,
                                        on_complete=lambda sp, t2:
                                        events.append(("b", t2)))))
    tl.run()
    assert events == [("timer", 0.5), ("a", 1.0), ("b", 1.5)]


def test_run_chain_executes_sequentially_after_offset():
    class T:
        def __init__(self, dur, byts):
            self.dur, self.byts = dur, byts

    tl = ContentionTimeline(bandwidth=1e9)
    seen = []
    tl.run_chain([T(1.0, 10.0), T(2.0, 10.0)], offset=0.5, key="p0",
                 on_task_done=lambda i, t: seen.append((i, t)))
    tl.run()
    assert seen == [(0, 1.5), (1, 3.5)]


def test_run_until_and_stop_predicate():
    tl = ContentionTimeline(bandwidth=1e9)
    for _ in range(3):
        tl.start(1.0, 1.0)
    assert tl.run(until=0.0) == 0.0          # deadline before any progress
    n = []
    tl2 = ContentionTimeline(bandwidth=1e9)
    tl2.start(1.0, 1.0, on_complete=lambda sp, t: n.append(t))
    tl2.start(5.0, 1.0)
    tl2.run(stop=lambda: bool(n))
    assert n == [1.0] and len(tl2.spans) == 1  # second span abandoned


def test_span_demand_property():
    assert Span(duration=2.0, byts=50.0).demand == pytest.approx(25.0)


# ---------------------------------------------------------------------------
# refactor equivalence: pre-refactor traces pinned
# ---------------------------------------------------------------------------

# Captured from the pre-refactor inline loops (commit ab3bfb9) with the
# exact calls below; the timeline rebuild must reproduce them.
_SWEEP_GOOGLENET_REF = {
    1: dict(perf=1.0, bw_mean=83157657501.18536, bw_std=100486185782.48589),
    2: dict(perf=1.0598918942150461, bw_mean=82668424001.35612,
            bw_std=84160407955.29362),
    4: dict(perf=1.0904512340597554, bw_mean=86454228075.12486,
            bw_std=79331600096.92084),
    8: dict(perf=1.1084091369382743, bw_mean=89366822336.34915,
            bw_std=56968825835.578156),
}

_TRACE_REF = {
    ("P1", "none"): dict(bw_mean=3615202671827.843,
                         bw_std=1487664451229.6973,
                         elapsed=9.558792488882855e-06),
    ("P4", "uniform"): dict(bw_mean=5016237111000.163,
                            bw_std=0.08325787180213622,
                            elapsed=1.5129587752066205e-05,
                            base_bw_mean=3670671627777.8438,
                            base_bw_std=1483839998721.2075),
    ("P4", "demand"): dict(bw_mean=5016237111000.158,
                           bw_std=0.08943617154923204,
                           elapsed=1.561254514665168e-05,
                           base_bw_mean=3640473880287.244,
                           base_bw_std=1485792855418.414),
}


@pytest.mark.slow
def test_simulate_reproduces_prerefactor_fig5_sweep():
    from repro.core.shaping_sim import partition_sweep
    from repro.models.cnn import model_traces

    rows = partition_sweep(model_traces("googlenet"), [2, 4, 8],
                           total_batch=64, n_passes=4)
    for p, ref in _SWEEP_GOOGLENET_REF.items():
        for k, v in ref.items():
            assert rows[p][k] == pytest.approx(v, rel=1e-9), (p, k)


def test_simulate_tasks_reproduces_prerefactor_serving_trace():
    from repro.configs import get_config
    from repro.serving import serving_trace_report

    cfg = get_config("qwen2-7b", smoke=True)
    for (pname, policy), ref in _TRACE_REF.items():
        rep = serving_trace_report(cfg, partitions=int(pname[1:]),
                                   policy=policy, total_slots=16,
                                   n_requests=64, prompt_len=32, gen=16)
        scale = ref["bw_mean"]
        for k, v in ref.items():
            # near-zero stds on a ~5e12 B/s mean are FP noise: compare with
            # an absolute floor proportional to the trace's magnitude
            assert rep[k] == pytest.approx(v, rel=1e-6, abs=1e-9 * scale), \
                (pname, policy, k)


def test_backcompat_reexports_from_shaping_sim():
    from repro.core import shaping_sim

    assert shaping_sim.maxmin_fair is maxmin_fair
    assert shaping_sim._bin_bw_samples is not None
