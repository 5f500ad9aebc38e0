"""serving.scheduler.EventScheduler: the event-driven virtual clock.

Pins the PR's acceptance gates:
  * P=1 equivalence — with one partition and an uncontended pipe the event
    clock and the lockstep clock must agree EXACTLY on every request's
    first-token and completion time (the clocks only diverge through
    cross-partition overlap and contention stretch, neither of which
    exists at P=1 uncontended);
  * gap closure — on the wave-granular Fig. 5 load, staggered policies'
    P=4 virtual throughput under the event clock is >= lockstep's and
    sits closer to the fluid simulation's ``perf_rel`` (the old timing
    ground truth) than lockstep does;
  * live shaping — P=4 demand-staggered steady-state bandwidth-demand std
    stays below the P=1 synchronous baseline on the event clock (the
    serving Fig. 5 analogue holds on the new clock);
  * policy semantics on the event clock — compute-bound prefill spans are
    serialized under uniform/demand while decode overlaps freely.
"""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import hw
from repro.serving import (EventScheduler, PhaseStaggeredScheduler,
                           RequestQueue, SimulatedEngine, make_scheduler)
from repro.serving.engine import decode_cost, prefill_cost
from repro.serving.trace_sim import (phase_balanced_bandwidth,
                                     serving_trace_report)


def _cfg():
    return get_config("qwen2-7b", smoke=True)


def _load(queue, n, prompt_len=8, gen=4):
    rng = np.random.default_rng(0)
    for _ in range(n):
        queue.submit(rng.integers(1, 100, size=(prompt_len,))
                     .astype(np.int32), gen)


def _fleet(cfg, partitions, slots=2, max_len=64, wave_only=False):
    return [SimulatedEngine(cfg, slots=slots, max_len=max_len, pid=p,
                            peak_flops=hw.TPU_PEAK_FLOPS / partitions,
                            wave_only=wave_only)
            for p in range(partitions)]


def _wave_time(cfg, partitions, total_slots, prompt_len, gen):
    slots = max(total_slots // partitions, 1)
    peak = hw.TPU_PEAK_FLOPS / partitions
    return (prefill_cost(cfg, slots, prompt_len, peak).duration
            + gen * decode_cost(cfg, slots, prompt_len + gen // 2,
                                peak).duration)


# ---------------------------------------------------------------------------
# P=1 equivalence: the two clocks must agree exactly
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ["none", "uniform", "demand"])
def test_p1_uncontended_event_matches_lockstep_exactly(policy):
    """Single partition, pipe wider than any demand: request completion
    and first-token times must be identical under both clocks (refills
    included: 7 requests through 2 slots forces 5 slot refills)."""
    cfg = _cfg()
    times = {}
    for clock in ("lockstep", "event"):
        q = RequestQueue()
        _load(q, 7)
        sched = make_scheduler(_fleet(cfg, 1), q, policy=policy,
                               bandwidth=1e30, clock=clock)
        m = sched.run()
        assert len(q.completed) == 7
        times[clock] = sorted((r.rid, r.t_first_token, r.t_done)
                              for r in q.completed)
    for (ra, fa, da), (rb, fb, db) in zip(times["lockstep"],
                                          times["event"]):
        assert ra == rb
        assert fa == pytest.approx(fb, rel=1e-12, abs=1e-30)
        assert da == pytest.approx(db, rel=1e-12, abs=1e-30)


def test_p1_wave_only_event_matches_lockstep_exactly():
    cfg = _cfg()
    times = {}
    for clock in ("lockstep", "event"):
        q = RequestQueue()
        _load(q, 8)
        sched = make_scheduler(_fleet(cfg, 1, wave_only=True), q,
                               policy="none", bandwidth=1e30, clock=clock)
        sched.run()
        assert len(q.completed) == 8
        times[clock] = sorted((r.rid, r.t_done) for r in q.completed)
    assert times["lockstep"] == pytest.approx(times["event"])


# ---------------------------------------------------------------------------
# completion semantics under the event clock
# ---------------------------------------------------------------------------


def test_event_clock_completes_all_with_refills():
    cfg = _cfg()
    q = RequestQueue()
    _load(q, 13, gen=5)
    eng = _fleet(cfg, 1, slots=2)[0]
    m = EventScheduler([eng], q, policy="none",
                       bandwidth=hw.TPU_HBM_BW).run()
    done = sorted(q.completed, key=lambda r: r.rid)
    assert len(done) == 13
    assert all(len(r.tokens) == r.max_new_tokens for r in done)
    assert eng.assign_order == sorted(eng.assign_order)  # FIFO preserved
    assert eng.pool.n_live == 0
    assert m.completed_tokens == 13 * 5
    assert m.virtual_seconds > 0


def test_open_arrivals_between_spans_fill_more_than_one_slot():
    """Requests submitted one per scheduler event, as an open-loop client
    does, while the engine is busy: each joins the running batch at the
    next commit, so more than one slot decodes at once, and every request
    is served in arrival order.  (Seating only into slots a commit frees,
    the first request's wave of one would stay the only running slot.)"""
    q = RequestQueue()
    eng = _fleet(_cfg(), 1, slots=4)[0]
    sched = make_scheduler([eng], q, policy="uniform", clock="event")
    rng = np.random.default_rng(3)
    n, most = 8, 0
    for _ in range(200):
        if q.n_submitted < n:
            q.submit(rng.integers(1, 100, size=(8,)).astype(np.int32), 6)
        if not (eng.busy or eng.backlog or len(q)):
            break
        sched.run(max_spans=1)
        most = max(most, sum(r is not None for r in eng.active))
    done = sorted(q.completed, key=lambda r: r.rid)
    assert len(done) == n and q.n_submitted == n
    assert all(len(r.tokens) == r.max_new_tokens for r in done)
    assert most > 1 and eng.n_free_seats > 0
    assert eng.n_prefills == 1            # it never drained until the end
    assert eng.assign_order == list(range(n))
    firsts = [r.t_first_token for r in done]
    assert firsts == sorted(firsts)


def test_event_spans_overlap_across_partitions():
    """The whole point of the event clock: one partition's prefill is in
    flight while another's decode steps run — the per-span trace must show
    cross-partition overlap, which the lockstep tick could never record."""
    cfg = _cfg()
    q = RequestQueue()
    _load(q, 32, gen=6)
    sched = EventScheduler(_fleet(cfg, 4), q, policy="demand",
                           bandwidth=hw.TPU_HBM_BW)
    sched.run()
    assert len(q.completed) == 32
    overlaps = 0
    prefills = [s for s in sched.trace if s.phase == "prefill"]
    decodes = [s for s in sched.trace if s.phase == "decode"]
    for p in prefills:
        for d in decodes:
            if d.pid != p.pid and d.t0 < p.t1 - 1e-18 \
                    and p.t0 < d.t1 - 1e-18:
                overlaps += 1
    assert overlaps > 0


@pytest.mark.parametrize("policy", ["uniform", "demand"])
def test_staggered_policies_serialize_prefill_spans(policy):
    """Compute-bound phases never overlap on the event clock: under the
    staggered policies at most one (non-refill) prefill span is in flight
    at any instant."""
    cfg = _cfg()
    q = RequestQueue()
    _load(q, 32, gen=4)
    sched = EventScheduler(_fleet(cfg, 4, wave_only=True), q, policy=policy,
                           bandwidth=hw.TPU_HBM_BW)
    sched.run()
    assert len(q.completed) == 32
    prefills = sorted((s.t0, s.t1) for s in sched.trace
                      if s.phase == "prefill")
    assert len(prefills) >= 4
    for (a0, a1), (b0, b1) in zip(prefills, prefills[1:]):
        assert b0 >= a1 - 1e-18, (a0, a1, b0, b1)


# ---------------------------------------------------------------------------
# the acceptance gate: gap closure + live shaping on the Fig. 5 load
# ---------------------------------------------------------------------------


def _wave_metrics(cfg, P, policy, clock, *, total_slots=16, n_requests=64,
                  prompt_len=32, gen=16, bandwidth=None):
    q = RequestQueue()
    _load(q, n_requests, prompt_len=prompt_len, gen=gen)
    sched = make_scheduler(
        _fleet(cfg, P, slots=max(total_slots // P, 1),
               max_len=prompt_len + 4 * gen, wave_only=True),
        q, policy=policy, bandwidth=bandwidth, clock=clock)
    m = sched.run()
    assert len(q.completed) == n_requests
    return m


def test_event_clock_closes_staggered_throughput_gap():
    cfg = _cfg()
    kw = dict(total_slots=16, n_requests=64, prompt_len=32, gen=16)
    bw = phase_balanced_bandwidth(cfg, **{k: kw[k] for k in
                                          ("total_slots", "prompt_len",
                                           "gen")})
    rel = {}
    for clock in ("lockstep", "event"):
        base = _wave_metrics(cfg, 1, "none", clock, bandwidth=bw, **kw)
        m = _wave_metrics(cfg, 4, "demand", clock, bandwidth=bw, **kw)
        rel[clock] = m.throughput() / base.throughput()
        if clock == "event":
            # (c) event-clock virtual throughput >= lockstep's
            assert m.throughput() >= rel["lockstep"] * \
                base.throughput() * (1 - 1e-9)
    sim = serving_trace_report(cfg, partitions=4, policy="demand",
                               bandwidth=bw, **kw)["perf_rel"]
    # the event clock sits closer to the fluid-sim ground truth
    assert abs(rel["event"] - sim) < abs(rel["lockstep"] - sim)


def test_event_clock_p4_demand_std_below_p1_sync_baseline():
    """The serving Fig. 5 analogue on the live event clock: steady-state
    (one wave trimmed per end) aggregate bandwidth-demand std of the P=4
    demand-staggered fleet is below the P=1 synchronous baseline, while
    the P=4 'none' (phase-aligned) fleet's is above it."""
    cfg = _cfg()
    kw = dict(total_slots=16, n_requests=64, prompt_len=32, gen=16)
    bw = phase_balanced_bandwidth(cfg, **{k: kw[k] for k in
                                          ("total_slots", "prompt_len",
                                           "gen")})
    trim1 = _wave_time(cfg, 1, kw["total_slots"], kw["prompt_len"],
                       kw["gen"])
    trim4 = 1.5 * _wave_time(cfg, 4, kw["total_slots"], kw["prompt_len"],
                             kw["gen"])
    base = _wave_metrics(cfg, 1, "none", "event", bandwidth=bw, **kw)
    staggered = _wave_metrics(cfg, 4, "demand", "event", bandwidth=bw, **kw)
    aligned = _wave_metrics(cfg, 4, "none", "event", bandwidth=bw, **kw)
    base_std = base.bw_stats(trim=trim1)[1]
    assert staggered.bw_stats(trim=trim4)[1] < base_std
    assert aligned.bw_stats(trim=trim4)[1] > base_std


# ---------------------------------------------------------------------------
# plumbing
# ---------------------------------------------------------------------------


def test_make_scheduler_validates_clock_and_policy():
    cfg = _cfg()
    q = RequestQueue()
    with pytest.raises(ValueError, match="clock"):
        make_scheduler(_fleet(cfg, 1), q, clock="sundial")
    with pytest.raises(ValueError, match="policy"):
        make_scheduler(_fleet(cfg, 1), q, policy="chaotic", clock="event")
    assert isinstance(make_scheduler(_fleet(cfg, 1), q, clock="lockstep"),
                      PhaseStaggeredScheduler)
    assert isinstance(make_scheduler(_fleet(cfg, 1), q, clock="event"),
                      EventScheduler)


def test_metrics_span_overlay_reduces_to_ticks_when_disjoint():
    from repro.serving.metrics import ServingMetrics

    a, b = ServingMetrics(), ServingMetrics()
    for t, dt, d in [(0.0, 1.0, 10.0), (1.0, 2.0, 30.0), (3.0, 1.0, 20.0)]:
        a.observe_tick(t, dt, d)
        b.observe_span(t, dt, d)
    assert a.bw_demand_mean == pytest.approx(b.bw_demand_mean)
    assert a.bw_demand_std == pytest.approx(b.bw_demand_std)
    # hand-check: time-weighted mean over [0,4] = (10+60+20)/4
    assert a.bw_demand_mean == pytest.approx(22.5)


def test_metrics_overlapping_spans_aggregate():
    from repro.serving.metrics import ServingMetrics

    m = ServingMetrics()
    m.observe_span(0.0, 2.0, 10.0)   # [0,2) at 10
    m.observe_span(1.0, 2.0, 30.0)   # [1,3) at 30 -> [1,2) sums to 40
    assert m.bw_demand_mean == pytest.approx((10 + 40 + 30) / 3)


def test_bw_stats_trim_swallowing_trace_returns_empty_stats():
    """Hardening: a trim window that meets or exceeds the trace span means
    no steady state was observed — (0, 0), never NaN, never a silently
    untrimmed answer."""
    from repro.serving.metrics import ServingMetrics

    m = ServingMetrics()
    m.observe_span(0.0, 1.0, 10.0)
    m.observe_span(1.0, 1.0, 30.0)   # trace span: [0, 2]
    assert m.bw_stats(trim=0.0) == pytest.approx((20.0, 10.0))
    for trim in (1.0, 1.5, 2.0, 100.0):   # 2*trim >= span
        mean, std = m.bw_stats(trim=trim)
        assert (mean, std) == (0.0, 0.0), trim
        assert not (np.isnan(mean) or np.isnan(std))
    # a sane trim still trims
    assert m.bw_stats(trim=0.25) == m.bw_stats(trim=0.0)  # centres survive


def test_bw_stats_empty_trace_is_zero():
    from repro.serving.metrics import ServingMetrics

    m = ServingMetrics()
    assert m.bw_stats() == (0.0, 0.0)
    assert m.bw_stats(trim=5.0) == (0.0, 0.0)


def test_achieved_bw_stats_degenerate_traces():
    """Same hardening for the allocated-bandwidth observable (shared by
    EventScheduler and the cluster controller)."""
    from repro.serving.metrics import achieved_bw_stats

    # empty trace / zero-length clock
    assert achieved_bw_stats([], 0.0) == (0.0, 0.0)
    assert achieved_bw_stats([], 1.0, trim=10.0) == (0.0, 0.0)
    assert achieved_bw_stats([(0.0, 1.0, 5.0)], 0.0) == (0.0, 0.0)
    # trim >= trace span
    samples = [(0.0, 1.0, 5.0), (1.0, 2.0, 15.0)]
    for trim in (1.0, 2.0, 50.0):
        mean, std = achieved_bw_stats(samples, 2.0, trim=trim)
        assert (mean, std) == (0.0, 0.0), trim
    # untrimmed and sanely-trimmed stats stay finite and positive
    mean, std = achieved_bw_stats(samples, 2.0, window=0.5)
    assert mean == pytest.approx(10.0) and std == pytest.approx(5.0)
    mean_t, _ = achieved_bw_stats(samples, 2.0, window=0.5, trim=0.5)
    assert np.isfinite(mean_t) and mean_t > 0
    # regression: a trim excluding EVERY window centre (but < half the
    # span) reports empty-trace stats, never a silently untrimmed average
    assert achieved_bw_stats(samples, 2.0, window=0.5,
                             trim=0.8) == (0.0, 0.0)


def test_event_scheduler_achieved_bw_stats_overtrim_is_empty():
    cfg = _cfg()
    q = RequestQueue()
    _load(q, 4)
    sched = EventScheduler(_fleet(cfg, 1), q, policy="none",
                           bandwidth=hw.TPU_HBM_BW)
    sched.run()
    t_end = sched.timeline.now
    assert sched.achieved_bw_stats()[0] > 0
    assert sched.achieved_bw_stats(trim=t_end) == (0.0, 0.0)
    assert sched.achieved_bw_stats(trim=t_end / 2) == (0.0, 0.0)
