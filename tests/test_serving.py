"""repro.serving: queue admission, continuous-batching slot refill,
phase-staggered scheduling, and the serving-trace shaping validation
(the serving analogue of the paper's Fig. 5 gates)."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import hw
from repro.serving import (PhaseStaggeredScheduler, RequestQueue,
                           SimulatedEngine, decode_cost, prefill_cost,
                           serving_trace_report)


def _cfg():
    return get_config("qwen2-7b", smoke=True)


def _load(queue, n, prompt_len=8, gen=4, deadline=None):
    rng = np.random.default_rng(0)
    return [queue.submit(rng.integers(1, 100, size=(prompt_len,))
                         .astype(np.int32), gen, deadline=deadline)
            for _ in range(n)]


def _fleet(cfg, partitions, slots=2, max_len=64):
    return [SimulatedEngine(cfg, slots=slots, max_len=max_len, pid=p,
                            peak_flops=hw.TPU_PEAK_FLOPS / partitions)
            for p in range(partitions)]


# ---------------------------------------------------------------------------
# queue: admission control
# ---------------------------------------------------------------------------


def test_queue_depth_admission():
    q = RequestQueue(max_depth=3)
    admitted = _load(q, 5)
    assert [r is not None for r in admitted] == [True] * 3 + [False] * 2
    assert q.n_rejected == 2 and len(q) == 3
    # FIFO pop preserves submission order
    assert [r.rid for r in q.pop(3)] == [0, 1, 2]


def test_queue_deadline_admission():
    # 1s of service per request: a 10s deadline is feasible, 0.1s is not
    q = RequestQueue(service_estimate=lambda r: 1.0)
    ok = q.submit(np.zeros(4, np.int32), 4, deadline=10.0)
    late = q.submit(np.zeros(4, np.int32), 4, deadline=0.1)
    assert ok is not None and late is None
    assert q.n_rejected == 1


def test_queue_depth_rejection_recovers_after_pop():
    """A bounded queue rejects at the bound, then admits again once depth
    frees up — and the rejected request got no rid (rids stay dense over
    ADMITTED requests only)."""
    q = RequestQueue(max_depth=2)
    a = q.submit(np.zeros(4, np.int32), 4)
    b = q.submit(np.zeros(4, np.int32), 4)
    assert q.submit(np.zeros(4, np.int32), 4) is None  # at the bound
    q.pop(1)
    c = q.submit(np.zeros(4, np.int32), 4)
    assert c is not None
    assert [a.rid, b.rid, c.rid] == [0, 1, 2]
    assert q.n_submitted == 3 and q.n_rejected == 1


def test_queue_deadline_accounts_for_arrival():
    """Feasibility is measured from the request's own arrival: the same
    absolute deadline is feasible at arrival 0 and infeasible for a
    request arriving 9.5s in (1s of service, deadline t=10)."""
    q = RequestQueue(service_estimate=lambda r: 1.0)
    early = q.submit(np.zeros(4, np.int32), 4, arrival=0.0, deadline=10.0)
    late = q.submit(np.zeros(4, np.int32), 4, arrival=9.5, deadline=10.0)
    assert early is not None and late is None


def test_queue_requeue_readmits_at_front():
    """The cluster failure handler's path: requeued requests go back to
    the FRONT (they must not lose their place), keep their rids and
    arrival/deadline accounting, bypass admission control even at the
    depth bound, and are served before newer work."""
    q = RequestQueue(max_depth=3)
    reqs = _load(q, 3, deadline=50.0)
    popped = q.pop(2)              # a worker took two requests...
    q.requeue(popped)              # ...and died
    assert q.n_requeued == 2
    assert len(q) == 3             # back at the bound
    # the depth bound still rejects NEW submissions while requeued work
    # holds the queue — only requeue itself bypasses admission
    assert q.submit(np.zeros(4, np.int32), 4) is None
    assert [r.rid for r in q.pop(3)] == [0, 1, 2]  # front, FIFO restored
    assert all(r.deadline == 50.0 and r.arrival == 0.0 for r in reqs[:2])


def test_sequential_requeues_restore_admission_order():
    """Two workers dying in the wrong order must not let the later (newer)
    requests jump the earlier (older) ones: requeue restores global
    admission order."""
    q = RequestQueue()
    _load(q, 6)
    worker_a = q.pop(2)            # rids 0, 1 (oldest)
    worker_b = q.pop(2)            # rids 2, 3
    q.requeue(worker_b)            # the NEWER worker dies first...
    q.requeue(worker_a)            # ...then the older one
    assert [r.rid for r in q.pop(6)] == [0, 1, 2, 3, 4, 5]


# ---------------------------------------------------------------------------
# phase-cost premise: prefill compute-bound, decode bandwidth-bound
# ---------------------------------------------------------------------------


def test_decode_demands_more_bandwidth_than_prefill():
    cfg = _cfg()
    pre = prefill_cost(cfg, 4, 32)
    dec = decode_cost(cfg, 4, 40)
    assert dec.demand > pre.demand  # the attn/BN analogue the paper needs
    assert pre.duration > dec.duration


# ---------------------------------------------------------------------------
# continuous batching: slot refill ordering + completion
# ---------------------------------------------------------------------------


def test_slot_refill_preserves_order_and_completes_all():
    cfg = _cfg()
    q = RequestQueue()
    _load(q, 7, gen=4)
    eng = _fleet(cfg, 1, slots=2, max_len=64)[0]
    m = PhaseStaggeredScheduler([eng], q, policy="none").run(max_ticks=500)
    done = sorted(q.completed, key=lambda r: r.rid)
    assert len(done) == 7
    assert all(len(r.tokens) == r.max_new_tokens for r in done)
    # service order is FIFO (the refill invariant)
    assert eng.assign_order == sorted(eng.assign_order)
    # refill actually happened: more requests served than prefill waves
    # could seat (2 slots/wave), so some slots were handed on mid-wave
    assert eng.n_prefills < len(done) / 2 + 1
    assert eng.n_refills == 5          # 7 requests, 2 wave seats
    # later submissions never finish before earlier ones start decoding
    t_done = [r.t_done for r in done]
    assert all(a <= b + 1e-12 for a, b in zip(t_done, t_done[1:]))
    assert m.completed_tokens == 7 * 4
    # per-slot refill: a refilled request's first token comes from its OWN
    # slot prefill — strictly after the slot freed, by at least one
    # single-prompt prefill duration (never the shared wave boundary)
    dur = prefill_cost(cfg, 1, 8, eng.peak_flops).duration
    by_rid = {r.rid: r for r in done}
    for rid in range(2, 7):
        pred = by_rid[rid - 2]         # previous occupant of the same slot
        assert by_rid[rid].t_first_token >= \
            pred.t_done + dur * (1 - 1e-9)
    # every block went back to the pool once the fleet drained
    assert eng.pool.n_live == 0


def test_refill_completing_on_first_token_retires_immediately():
    """A refilled request whose prefill-emitted first token exhausts its
    budget (max_new_tokens=1) must retire in the same tick — never decode
    past its budget — and its slot chains to the next backlog request."""
    cfg = _cfg()
    q = RequestQueue()
    rng = np.random.default_rng(0)
    for gen in (1, 6, 1, 1, 2):
        q.submit(rng.integers(1, 100, size=(8,)).astype(np.int32), gen)
    eng = _fleet(cfg, 1, slots=2, max_len=64)[0]
    PhaseStaggeredScheduler([eng], q, policy="none").run(max_ticks=200)
    done = sorted(q.completed, key=lambda r: r.rid)
    assert len(done) == 5
    assert all(len(r.tokens) == r.max_new_tokens for r in done)
    assert eng.assign_order == sorted(eng.assign_order)
    assert eng.pool.n_live == 0


def test_refill_ttft_prices_own_prompt_not_wave():
    """Two waves of different prompt lengths: the refilled (longer) request
    pays ITS prompt's prefill in TTFT, not the seated wave's."""
    cfg = _cfg()
    q = RequestQueue()
    _load(q, 2, prompt_len=8, gen=4)
    _load(q, 1, prompt_len=32, gen=4)
    eng = _fleet(cfg, 1, slots=2, max_len=64)[0]
    PhaseStaggeredScheduler([eng], q, policy="none").run(max_ticks=200)
    done = {r.rid: r for r in q.completed}
    assert len(done) == 3 and eng.n_refills == 1
    long_dur = prefill_cost(cfg, 1, 32, eng.peak_flops).duration
    short_dur = prefill_cost(cfg, 1, 8, eng.peak_flops).duration
    gap = done[2].t_first_token - done[0].t_done
    assert gap >= long_dur * (1 - 1e-9)   # billed its own 32-token prefill
    assert long_dur > 2 * short_dur       # ...which is not the wave's price


# ---------------------------------------------------------------------------
# admission into free slots: a busy engine seats its backlog into every
# empty slot at each commit, not only into the slots the commit freed
# ---------------------------------------------------------------------------


def _submit(queue, gens, prompt_lens=None):
    rng = np.random.default_rng(1)
    lens = prompt_lens or [8] * len(gens)
    return [queue.submit(rng.integers(1, 100, size=(n,)).astype(np.int32), g)
            for n, g in zip(lens, gens)]


def _free_seats(eng) -> float:
    return dict(eng.metrics_snapshot())["engine.free_slot_seats"]


def _wave_of_one(q, eng, gen=6):
    """Seat one request by a wave; the engine's other slots stay empty."""
    _submit(q, [gen])
    eng.assign(q.pop(1))
    eng.prefill_wave(0.0)
    assert [r is not None for r in eng.active] == \
        [True] + [False] * (eng.slots - 1)


def test_busy_engine_seats_backlog_into_every_free_slot():
    """A wave of one, then three requests assigned while the engine is
    busy: the next commit seats all three, FIFO, into the empty slots (a
    busy engine never drains, so a wave would never seat them)."""
    q = RequestQueue()
    eng = _fleet(_cfg(), 1, slots=4)[0]
    _wave_of_one(q, eng)
    _submit(q, [6, 6, 6])
    eng.assign(q.pop(3))
    assert not eng.wants_prefill
    eng.decode_step(1.0)
    assert [r.rid for r in eng.active] == [0, 1, 2, 3]
    assert eng.assign_order == [0, 1, 2, 3] and eng.backlog == []
    assert eng.n_refills == 3 and _free_seats(eng) == 3
    eng.decode_step(2.0)            # all four decode in one step
    assert [len(r.tokens) for r in eng.active] == [3, 2, 2, 2]


def test_free_slot_seating_stops_at_the_first_request_the_pool_cannot_hold():
    """FIFO with no skipping: a head the pool cannot hold stays queued,
    and so does the small request behind it, though it would fit."""
    q = RequestQueue()
    # blocks of 8: the first request takes 2, the big one needs 6, the
    # small one 2; 6 usable blocks leave 4 free while the first decodes
    eng = SimulatedEngine(_cfg(), slots=4, max_len=64, block_size=8,
                          pool_blocks=7, peak_flops=hw.TPU_PEAK_FLOPS)
    _wave_of_one(q, eng, gen=8)
    _submit(q, [8, 4], prompt_lens=[40, 8])
    big, small = q.pop(2)
    eng.assign([big, small])
    eng.decode_step(1.0)
    assert eng.pool.n_free == 4 and eng.backlog == [big, small]
    assert eng.active[1:] == [None] * 3 and _free_seats(eng) == 0
    t = 2.0
    while eng.busy:
        eng.decode_step(t)
        t += 1.0
    assert len(eng.completed) == 3
    assert eng.assign_order == [0, big.rid, small.rid]
    assert eng.pool.n_live == 0


def test_newcomer_done_on_its_first_token_frees_the_free_slot_again():
    """A request seated into an empty slot whose first token ends it
    retires in the same commit, and the next request takes that slot;
    only the first seating counts as a free-slot seat."""
    q = RequestQueue()
    eng = _fleet(_cfg(), 1, slots=4)[0]
    _wave_of_one(q, eng)
    one, nxt = _submit(q, [1, 3])
    eng.assign(q.pop(2))
    eng.decode_step(1.0)
    assert one.done and one in eng.completed and len(one.tokens) == 1
    assert eng.active[1] is nxt and eng.active[2:] == [None, None]
    assert eng.n_refills == 2 and _free_seats(eng) == 1


def test_wave_only_engine_seats_only_at_waves():
    q = RequestQueue()
    eng = SimulatedEngine(_cfg(), slots=4, max_len=64, wave_only=True,
                          peak_flops=hw.TPU_PEAK_FLOPS)
    _wave_of_one(q, eng, gen=3)
    _submit(q, [3, 3, 3])
    eng.assign(q.pop(3))
    t = 1.0
    while eng.busy:
        eng.decode_step(t)
        t += 1.0
        assert eng.active[1:] == [None] * 3 and len(eng.backlog) == 3
    assert eng.wants_prefill
    eng.prefill_wave(t)
    assert [r is not None for r in eng.active] == [True] * 3 + [False]
    assert eng.n_refills == 0 and _free_seats(eng) == 0


@pytest.mark.parametrize("slots, gens, refills", [
    (2, [4] * 7, 5),
    (4, [3, 5, 2, 4, 6, 1, 3, 2, 4, 5], 6),
])
def test_offline_full_waves_seat_as_before(slots, gens, refills):
    """An offline backlog fills every slot at the wave, so each refill
    goes into a slot the same commit freed: FIFO service, one wave, the
    refill count of per-slot refill, and no free-slot seat."""
    q = RequestQueue()
    _submit(q, gens)
    eng = _fleet(_cfg(), 1, slots=slots)[0]
    PhaseStaggeredScheduler([eng], q, policy="none").run(max_ticks=500)
    assert len(q.completed) == len(gens)
    assert eng.assign_order == list(range(len(gens)))
    assert eng.n_prefills == 1 and eng.n_refills == refills
    assert _free_seats(eng) == 0


# ---------------------------------------------------------------------------
# scheduler phase staggering
# ---------------------------------------------------------------------------


def test_demand_policy_non_overlapping_prefill_phases():
    cfg = _cfg()
    q = RequestQueue()
    _load(q, 32, gen=4)
    sched = PhaseStaggeredScheduler(_fleet(cfg, 4), q, policy="demand")
    sched.run(max_ticks=2000)
    prefills = [rec.phases.count("prefill") for rec in sched.trace]
    assert max(prefills) == 1  # compute-bound phases never overlap
    # phases interleave: some ticks mix one prefill with running decodes
    assert any(rec.phases.count("prefill") == 1
               and rec.phases.count("decode") >= 1 for rec in sched.trace)
    assert len(q.completed) == 32


def test_none_policy_aligns_phases():
    cfg = _cfg()
    q = RequestQueue()
    _load(q, 32, gen=4)
    sched = PhaseStaggeredScheduler(_fleet(cfg, 4), q, policy="none")
    sched.run(max_ticks=2000)
    assert any(rec.phases.count("prefill") >= 2 for rec in sched.trace)
    assert len(q.completed) == 32


def test_stall_fallback_spacing_state_scoped_to_demand_policy():
    """The forward-progress fallback in ``step`` must only touch the
    demand policy's ``_last_wave_start`` spacing state: under none/uniform
    the fallback (and normal operation) leaves it untouched, so switching
    a fleet between policies cannot inherit stale demand spacing."""
    cfg = _cfg()
    for policy, touched in [("none", False), ("uniform", False),
                            ("demand", True)]:
        q = RequestQueue()
        _load(q, 8, gen=3)
        sched = PhaseStaggeredScheduler(_fleet(cfg, 2), q, policy=policy)
        sched.run(max_ticks=500)
        assert len(q.completed) == 8
        assert bool(sched._last_wave_start > -float("inf")) == touched, \
            policy


@pytest.mark.parametrize("policy", ["uniform", "demand"])
def test_staggered_policies_interleave_phases_more(policy):
    """The scheduler's job is phase mixing: staggered policies spend more
    ticks with prefill and decode in flight simultaneously than ``none``
    (whether mixing smooths the *timeline* is the fluid simulation's gate —
    the lockstep tick clock is too coarse to measure that here)."""
    cfg = _cfg()

    def mixed_ticks(pol):
        q = RequestQueue()
        _load(q, 48, gen=8)
        sched = PhaseStaggeredScheduler(_fleet(cfg, 4), q, policy=pol)
        sched.run(max_ticks=4000)
        assert len(q.completed) == 48
        return sum(1 for rec in sched.trace
                   if "prefill" in rec.phases and "decode" in rec.phases)

    assert mixed_ticks(policy) > mixed_ticks("none")


# ---------------------------------------------------------------------------
# serving-trace simulation: the Fig. 5 analogue gate
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ["uniform", "demand"])
def test_serving_sim_std_strictly_lower_p4_vs_p1(policy):
    rep = serving_trace_report(_cfg(), partitions=4, policy=policy,
                               total_slots=16, n_requests=64,
                               prompt_len=32, gen=16)
    assert rep["bw_std"] < rep["base_bw_std"]   # smoother
    assert rep["bw_mean"] > rep["base_bw_mean"]  # and better utilized


def test_serve_cli_partitioned_end_to_end():
    from repro.launch.serve import main
    outs = main(["--arch", "mamba2-130m", "--smoke", "--requests", "6",
                 "--batch", "2", "--partitions", "2", "--stagger", "demand",
                 "--prompt-len", "8", "--gen", "4"])
    assert len(outs) == 4  # 2 partitions x 2 slots
    assert sum(len(o) for o in outs) == 6 * 4
