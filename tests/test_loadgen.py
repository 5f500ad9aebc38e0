"""repro.serving.loadgen: the open-loop traffic model behind the soak.

Property tests (hypothesis, skipping cleanly when absent) pin the
statistical contracts the load harness sells:

  * every arrival process is seeded-deterministic, sorted, and confined
    to [0, horizon);
  * empirical rates track the nominal mean rate (Poisson tolerance);
  * bursty windows are DETERMINISTIC — phase(t) < duty decides burst
    membership, and the in-burst empirical intensity actually runs
    ``burst_ratio`` hotter than the trough;
  * diurnal intensity peaks half a period in and bottoms at t=0;
  * heavy-tailed lengths respect their bounds and land near the nominal
    median;
  * goodput arithmetic: rejected and late both count against, no-deadline
    completions count for;
  * open-loop injection end-to-end: ``schedule_arrivals`` drives a live
    cluster through idle gaps and bursts on the virtual clock.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serving.loadgen import (ARRIVALS, LengthMix, SloSpec,
                                   bursty_arrivals, bursty_rates,
                                   diurnal_arrivals, goodput_stats,
                                   heavy_tail_lengths, make_arrivals,
                                   make_trace, poisson_arrivals,
                                   schedule_arrivals)

# ---------------------------------------------------------------------------
# arrival processes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ARRIVALS)
def test_arrivals_seeded_sorted_bounded(kind):
    a = make_arrivals(kind, rate=500.0, horizon=2.0, seed=7)
    b = make_arrivals(kind, rate=500.0, horizon=2.0, seed=7)
    c = make_arrivals(kind, rate=500.0, horizon=2.0, seed=8)
    np.testing.assert_array_equal(a, b)      # same seed, same trace
    assert len(a) != len(c) or not np.array_equal(a, c)
    assert np.all(np.diff(a) >= 0)
    assert len(a) and a[0] >= 0.0 and a[-1] < 2.0


def test_make_arrivals_rejects_unknown_kind():
    with pytest.raises(ValueError, match="arrival kind"):
        make_arrivals("tsunami", 1.0, 1.0)


@pytest.mark.parametrize("kind", ARRIVALS)
def test_empirical_rate_tracks_nominal(kind):
    """Mean count over [0, H) ~= rate*H within 5 sigma of Poisson noise."""
    rate, horizon = 2000.0, 5.0
    n = len(make_arrivals(kind, rate, horizon, seed=3))
    mean = rate * horizon
    assert abs(n - mean) < 5.0 * np.sqrt(mean), (kind, n, mean)


def test_bursty_rates_mean_is_rate():
    hot, cold = bursty_rates(100.0, burst_ratio=8.0, duty=0.25)
    assert hot == pytest.approx(8.0 * cold)
    assert 0.25 * hot + 0.75 * cold == pytest.approx(100.0)
    with pytest.raises(ValueError, match="duty"):
        bursty_rates(1.0, 2.0, duty=1.0)
    with pytest.raises(ValueError, match="burst_ratio"):
        bursty_rates(1.0, 0.5, duty=0.25)


def test_bursty_windows_are_deterministic_and_hot():
    """Burst membership is pure arithmetic — phase(t) < duty — and the
    in-window empirical intensity runs ~burst_ratio over the trough."""
    rate, horizon, period, duty, ratio = 2000.0, 8.0, 1.0, 0.25, 8.0
    a = bursty_arrivals(rate, horizon, seed=5, burst_ratio=ratio,
                        duty=duty, period=period)
    in_burst = (a % period) / period < duty
    hot_rate = in_burst.sum() / (horizon * duty)
    cold_rate = (~in_burst).sum() / (horizon * (1.0 - duty))
    assert hot_rate / cold_rate == pytest.approx(ratio, rel=0.2)


def test_diurnal_peaks_half_period_in():
    """Intensity valley at t=0, peak at t=period/2; quarter-bin counts
    around the peak dominate the valley by ~peak_ratio."""
    rate, horizon, pr = 4000.0, 4.0, 4.0
    a = diurnal_arrivals(rate, horizon, seed=9, peak_ratio=pr,
                         period=horizon)
    phase = a / horizon
    valley = ((phase < 0.125) | (phase >= 0.875)).sum()
    peak = ((phase >= 0.375) & (phase < 0.625)).sum()
    assert peak / max(valley, 1) == pytest.approx(pr, rel=0.25)
    with pytest.raises(ValueError, match="peak_ratio"):
        diurnal_arrivals(1.0, 1.0, peak_ratio=0.5)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       rate=st.floats(10.0, 5000.0),
       horizon=st.floats(0.1, 4.0))
def test_poisson_properties(seed, rate, horizon):
    a = poisson_arrivals(rate, horizon, seed)
    np.testing.assert_array_equal(a, poisson_arrivals(rate, horizon, seed))
    assert np.all((a >= 0.0) & (a < horizon))
    assert np.all(np.diff(a) >= 0)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       duty=st.floats(0.05, 0.95),
       ratio=st.floats(1.0, 32.0))
def test_bursty_envelope_properties(seed, duty, ratio):
    """The thinning envelope holds for ANY knob setting: deterministic
    replay, bounded support, and the hot/cold identity
    duty*hot + (1-duty)*cold == rate."""
    hot, cold = bursty_rates(200.0, ratio, duty)
    assert hot >= cold > 0.0
    assert duty * hot + (1.0 - duty) * cold == pytest.approx(200.0)
    a = bursty_arrivals(200.0, 2.0, seed, burst_ratio=ratio, duty=duty,
                        period=0.5)
    np.testing.assert_array_equal(
        a, bursty_arrivals(200.0, 2.0, seed, burst_ratio=ratio, duty=duty,
                           period=0.5))
    assert np.all((a >= 0.0) & (a < 2.0))


# ---------------------------------------------------------------------------
# heavy-tailed lengths
# ---------------------------------------------------------------------------


def test_heavy_tail_lengths_bounds_and_median():
    x = heavy_tail_lengths(20000, seed=1, median=64.0, alpha=1.2,
                           lo=4, hi=4096)
    assert x.dtype == np.int64
    assert x.min() >= 4 and x.max() <= 4096
    assert np.median(x) == pytest.approx(64.0, rel=0.15)
    # heavy tail: the clipped max actually reaches far above the median
    assert x.max() > 16 * 64


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       median=st.floats(4.0, 256.0),
       alpha=st.floats(0.8, 3.0))
def test_heavy_tail_properties(seed, median, alpha):
    x = heavy_tail_lengths(256, seed, median=median, alpha=alpha,
                           lo=1, hi=8192)
    np.testing.assert_array_equal(
        x, heavy_tail_lengths(256, seed, median=median, alpha=alpha,
                              lo=1, hi=8192))
    assert x.min() >= 1 and x.max() <= 8192


def test_length_mix_seeds_are_independent():
    mix = LengthMix()
    p = mix.prompt_lengths(64, seed=0)
    g = mix.gen_lengths(64, seed=0)
    assert not np.array_equal(p[:len(g)], g)  # different distributions
    assert p.max() <= mix.prompt_max and g.max() <= mix.gen_max


# ---------------------------------------------------------------------------
# traces, SLOs, goodput arithmetic
# ---------------------------------------------------------------------------


def test_make_trace_is_deterministic_and_slo_stamped():
    slo = SloSpec(ttft_budget=2.0, tpot_budget=0.5)
    t1 = make_trace("poisson", 200.0, 1.0, seed=4, slo=slo, max_len=64)
    t2 = make_trace("poisson", 200.0, 1.0, seed=4, slo=slo, max_len=64)
    assert len(t1) == len(t2) > 0
    for a, b in zip(t1, t2):
        assert a.arrival == b.arrival and a.deadline == b.deadline
        np.testing.assert_array_equal(a.prompt, b.prompt)
    for r in t1:
        assert r.deadline == pytest.approx(
            r.arrival + 2.0 + 0.5 * r.max_new_tokens)
        # max_len caps the PROMPT around the decode budget (floor of 1)
        assert len(r.prompt) <= max(64 - r.max_new_tokens, 1)


def test_goodput_counts_rejects_and_late_against():
    class _Q:
        n_submitted, n_rejected = 4, 1

        class _R:
            def __init__(self, t_done, deadline):
                self.t_done, self.deadline = t_done, deadline

        completed = [_R(1.0, 2.0),    # on time
                     _R(3.0, 2.0),    # late
                     _R(1.0, None)]   # no deadline: counts when completed

    gs = goodput_stats(_Q())
    assert gs["offered"] == 5 and gs["attained"] == 2 and gs["late"] == 1
    assert gs["goodput"] == pytest.approx(2.0 / 5.0)


# ---------------------------------------------------------------------------
# open-loop injection end-to-end
# ---------------------------------------------------------------------------


def test_schedule_arrivals_drives_live_cluster():
    """The integration the soak depends on: arrivals land on the virtual
    clock mid-run, the pump picks them up, and goodput comes out of the
    same queue — through real idle gaps between bursts."""
    from repro.serving import RequestQueue, make_cluster, make_worker_specs

    slo = SloSpec(ttft_budget=1.0, tpot_budget=0.1)  # loose: all attained
    trace = make_trace("bursty", rate=4e6, horizon=4e-6, seed=2, slo=slo,
                       mix=LengthMix(prompt_median=8, prompt_max=16,
                                     gen_median=4, gen_max=8),
                       max_len=32, arrival_kw={"period": 1e-6})
    assert len(trace) > 4
    q = RequestQueue()
    ctl = make_cluster(make_worker_specs("qwen2-7b", 2, max_len=64), q,
                       transport="loopback", router="round_robin")
    n = schedule_arrivals(ctl.timeline, q, trace, on_arrival=ctl.pump)
    assert n == len(trace)
    ctl.run()
    gs = goodput_stats(q)
    assert gs["completed"] == len(trace)
    assert gs["goodput"] == pytest.approx(1.0)
    # open-loop: completions start before the last arrival lands
    assert min(r.t_done for r in q.completed) < trace[-1].arrival
