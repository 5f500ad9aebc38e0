"""repro.serving.cluster: controller-routed multi-process partition workers.

Pins the PR's acceptance gates:
  * protocol completeness — every message encode/decode round-trips
    through plain primitives (nothing crosses by object reference);
  * loopback equivalence — the cluster over the loopback transport
    reproduces the in-process ``EventScheduler`` metrics EXACTLY:
    round_robin == policy 'none', shaping == policy 'demand' (identical
    request stamps and summary, wall-clock excluded);
  * real process boundary — a multiprocessing P=4 cluster serves the load
    end-to-end and its virtual-clock metrics equal the loopback run;
  * failure handling — killing a worker mid-run (deterministically via a
    virtual-clock timer, on BOTH transports) re-queues its unfinished
    requests with arrival/deadline preserved and the run completes with
    no lost requests;
  * shaping across the boundary — the P=4 shaping-routed cluster's
    steady-state bw-demand std stays below the P=1 in-process synchronous
    baseline (the serving Fig. 5 analogue, cluster-wide).
"""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import hw
from repro.serving import (EventScheduler, RequestQueue, SimulatedEngine,
                           make_cluster, make_worker_specs)
from repro.serving.cluster import protocol as P
from repro.serving.cluster import (ClusterError, LoopbackTransport,
                                   WorkerRuntime, make_router,
                                   make_transport)
from repro.serving.engine import decode_cost, prefill_cost
from repro.serving.trace_sim import phase_balanced_bandwidth

ARCH = "qwen2-7b"


def _cfg():
    return get_config(ARCH, smoke=True)


def _load(queue, n, prompt_len=8, gen=4, deadline=None):
    rng = np.random.default_rng(0)
    for _ in range(n):
        queue.submit(rng.integers(1, 100, size=(prompt_len,))
                     .astype(np.int32), gen, deadline=deadline)


def _fleet(cfg, partitions, slots=2, max_len=64, wave_only=False):
    return [SimulatedEngine(cfg, slots=slots, max_len=max_len, pid=p,
                            peak_flops=hw.TPU_PEAK_FLOPS / partitions,
                            wave_only=wave_only)
            for p in range(partitions)]


def _specs(partitions, slots=2, max_len=64, wave_only=False):
    return make_worker_specs(ARCH, partitions, slots=slots, max_len=max_len,
                             wave_only=wave_only)


def _stamps(queue):
    return sorted((r.rid, r.t_first_token, r.t_done)
                  for r in queue.completed)


def _summary_no_wall(m):
    return {k: v for k, v in m.summary().items() if "wall" not in k}


# ---------------------------------------------------------------------------
# protocol: serializable, complete
# ---------------------------------------------------------------------------


def test_protocol_messages_round_trip():
    status = P.WorkerStatus(busy=True, wants_prefill=False, backlog_len=3,
                            n_active=2, head_arrival=1.5, pre_dur=2e-6,
                            wave_dur=9e-6)
    msgs = [
        P.Assign(requests=(P.WireRequest(rid=7, prompt=(1, 2, 3),
                                         max_new_tokens=4, arrival=0.5,
                                         deadline=9.0),)),
        P.IssueOp(op="prefill"),
        P.CommitOp(t_end=1.25e-6),
        P.Ping(t_wall=123.0),
        P.Shutdown(),
        P.Hello(wid=2, slots=4, max_len=64, status=status),
        P.AssignAck(status=status),
        P.OpIssued(op="decode",
                   cost=P.WireCost(flops=1e9, byts=2e6, duration=3e-6),
                   status=status),
        P.OpCommitted(op="prefill",
                      retired=(P.RetiredRequest(rid=7, tokens=(1, 1, 2, 3),
                                                t_first_token=1e-6,
                                                t_done=4e-6),),
                      refill=P.WireCost(flops=1e8, byts=1e5, duration=1e-7),
                      status=status),
        P.OpCommitted(op="decode", retired=(), refill=None, status=status),
        P.Pong(t_wall=123.0, status=status),
        P.Bye(n_prefills=3, n_refills=1, n_decode_steps=20),
        P.WorkerError(error="ValueError: boom", traceback="tb"),
    ]
    for msg in msgs:
        wire = P.encode(msg)
        assert wire["kind"] == type(msg).__name__
        assert P.decode(wire) == msg


def test_wire_request_round_trips_request():
    from repro.serving.queue import Request

    req = Request(rid=3, prompt=np.array([5, 6, 7], np.int32),
                  max_new_tokens=2, arrival=1.0, deadline=4.0)
    back = P.WireRequest.from_request(req).to_request()
    assert back.rid == req.rid and back.max_new_tokens == 2
    assert back.arrival == 1.0 and back.deadline == 4.0
    np.testing.assert_array_equal(back.prompt, req.prompt)


def test_worker_status_reports_spacing_ingredients():
    """The shaping router's spacing rule is priced worker-side: a drained
    engine with backlog must report the same prefill/wave durations the
    in-process demand policy computes."""
    cfg = _cfg()
    eng = _fleet(cfg, 2)[0]
    q = RequestQueue()
    _load(q, 3)
    eng.assign(q.pop(3))
    st = WorkerRuntime(eng).status()
    assert st.wants_prefill and not st.busy and st.backlog_len == 3
    pre = eng.prefill_cost_est()
    wave = pre.duration + \
        eng.backlog[0].max_new_tokens * eng.decode_cost_est().duration
    assert st.pre_dur == pre.duration
    assert st.wave_dur == wave


# ---------------------------------------------------------------------------
# loopback equivalence: cluster == in-process EventScheduler, exactly
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("router,policy", [("round_robin", "none"),
                                           ("shaping", "demand")])
def test_loopback_cluster_matches_event_scheduler_exactly(router, policy):
    """The acceptance gate: the loopback-transport cluster reproduces the
    in-process event-clock fleet metric-for-metric — same request stamps,
    same virtual clock, same bandwidth-demand overlay (wall-clock times
    excluded, they measure different machinery)."""
    cfg = _cfg()
    q_ref = RequestQueue()
    _load(q_ref, 21)
    ref = EventScheduler(_fleet(cfg, 4), q_ref, policy=policy,
                         bandwidth=hw.TPU_HBM_BW)
    m_ref = ref.run()

    q_cl = RequestQueue()
    _load(q_cl, 21)
    ctl = make_cluster(_specs(4), q_cl, transport="loopback", router=router,
                       bandwidth=hw.TPU_HBM_BW)
    m_cl = ctl.run()

    assert len(q_cl.completed) == len(q_ref.completed) == 21
    assert _stamps(q_cl) == _stamps(q_ref)
    assert _summary_no_wall(m_cl) == _summary_no_wall(m_ref)
    assert ctl.timeline.now == ref.timeline.now


def test_loopback_cluster_matches_event_scheduler_wave_only():
    """Same gate on the wave-granular Fig. 5 load (every wave start is
    policy-gated), where the shaping stagger actually binds."""
    cfg = _cfg()
    q_ref = RequestQueue()
    _load(q_ref, 24, prompt_len=16, gen=6)
    ref = EventScheduler(_fleet(cfg, 4, wave_only=True), q_ref,
                         policy="demand", bandwidth=hw.TPU_HBM_BW)
    m_ref = ref.run()

    q_cl = RequestQueue()
    _load(q_cl, 24, prompt_len=16, gen=6)
    ctl = make_cluster(_specs(4, wave_only=True), q_cl,
                       transport="loopback", router="shaping",
                       bandwidth=hw.TPU_HBM_BW)
    m_cl = ctl.run()
    assert _stamps(q_cl) == _stamps(q_ref)
    assert _summary_no_wall(m_cl) == _summary_no_wall(m_ref)


def test_shortest_backlog_router_balances_and_completes():
    q = RequestQueue()
    _load(q, 26, gen=4)
    ctl = make_cluster(_specs(4), q, transport="loopback",
                       router="shortest_backlog", bandwidth=hw.TPU_HBM_BW)
    ctl.run()
    assert len(q.completed) == 26
    served = [len(ctl.transport.runtimes[w].engine.assign_order)
              for w in sorted(ctl.views)]
    assert min(served) > 0  # every worker took a share of the load


def test_make_router_validates():
    with pytest.raises(ValueError, match="router"):
        make_router("chaotic")
    with pytest.raises(ValueError, match="transport"):
        make_transport("carrier-pigeon", _specs(1))


# ---------------------------------------------------------------------------
# shaping across the cluster: the Fig. 5 analogue over the boundary
# ---------------------------------------------------------------------------


def _wave_time(cfg, partitions, total_slots, prompt_len, gen):
    slots = max(total_slots // partitions, 1)
    peak = hw.TPU_PEAK_FLOPS / partitions
    return (prefill_cost(cfg, slots, prompt_len, peak).duration
            + gen * decode_cost(cfg, slots, prompt_len + gen // 2,
                                peak).duration)


def test_cluster_shaping_std_below_p1_sync_baseline():
    """P=4 shaping-routed cluster steady-state bw-demand std < the P=1
    in-process synchronous baseline; the round_robin (phase-aligned)
    cluster sits above it."""
    cfg = _cfg()
    kw = dict(total_slots=16, n_requests=48, prompt_len=32, gen=16)
    bw = phase_balanced_bandwidth(cfg, total_slots=16, prompt_len=32,
                                  gen=16)
    trim1 = _wave_time(cfg, 1, 16, 32, 16)
    trim4 = 1.5 * _wave_time(cfg, 4, 16, 32, 16)

    q = RequestQueue()
    _load(q, kw["n_requests"], prompt_len=32, gen=16)
    base = EventScheduler(_fleet(cfg, 1, slots=16, max_len=32 + 64,
                                 wave_only=True), q, policy="none",
                          bandwidth=bw).run()
    base_std = base.bw_stats(trim=trim1)[1]

    stds = {}
    for router in ("shaping", "round_robin"):
        qc = RequestQueue()
        _load(qc, kw["n_requests"], prompt_len=32, gen=16)
        ctl = make_cluster(_specs(4, slots=4, max_len=32 + 64,
                                  wave_only=True), qc,
                           transport="loopback", router=router,
                           bandwidth=bw)
        m = ctl.run()
        assert len(qc.completed) == kw["n_requests"]
        stds[router] = m.bw_stats(trim=trim4)[1]
    assert stds["shaping"] < base_std
    assert stds["round_robin"] > base_std


# ---------------------------------------------------------------------------
# failure handling: kill a worker mid-run, nothing is lost
# ---------------------------------------------------------------------------


def test_loopback_worker_kill_requeues_and_completes():
    """Deterministic failover: a virtual-clock timer kills worker 1
    mid-run; its unfinished requests are re-queued (arrival/deadline
    preserved, generated tokens reset) and the survivors finish the whole
    load."""
    q = RequestQueue()
    _load(q, 24, gen=5)
    ctl = make_cluster(_specs(3), q, transport="loopback",
                       router="round_robin", bandwidth=hw.TPU_HBM_BW)
    ctl.timeline.call_at(1e-7, lambda t: ctl.transport.kill(1))
    m = ctl.run()
    assert ctl.n_failovers == 1 and ctl.failed_workers == [1]
    assert q.n_requeued > 0
    assert ctl.prefill_live == 0   # failover never unbalances the gate
    assert len(q.completed) == 24  # no lost requests
    assert all(len(r.tokens) == r.max_new_tokens for r in q.completed)
    assert all(r.t_first_token is not None and r.t_done is not None
               for r in q.completed)
    assert not ctl.views[1].outstanding
    # the dead worker served nothing after the kill instant
    assert all(s.t0 <= 1e-7 + 1e-12 for s in ctl.trace if s.pid == 1)


def test_requeued_requests_keep_arrival_and_deadline():
    q = RequestQueue()
    deadline = 1e6  # loose: feasible, but must survive the failover
    _load(q, 12, gen=4, deadline=deadline)
    ctl = make_cluster(_specs(2), q, transport="loopback",
                       router="round_robin", bandwidth=hw.TPU_HBM_BW)
    ctl.timeline.call_at(1e-7, lambda t: ctl.transport.kill(0))
    ctl.run()
    assert len(q.completed) == 12
    assert all(r.arrival == 0.0 and r.deadline == deadline
               for r in q.completed)
    assert ctl.metrics.deadline_misses == 0


def test_kill_during_shaping_keeps_prefill_gate_balanced():
    """Regression: a worker dying while its span is in the current step's
    completion batch must not double-decrement the prefill-in-flight
    counter (the span's own completion callback does the bookkeeping when
    the cancel misses) — otherwise the shaping router's at-most-one-
    prefill gate silently admits concurrent prefills after a failover."""
    for kill_t in (1e-9, 1e-8, 1e-7, 5e-7, 1e-6):
        q = RequestQueue()
        _load(q, 20, gen=5)
        ctl = make_cluster(_specs(2, wave_only=True), q,
                           transport="loopback", router="shaping",
                           bandwidth=hw.TPU_HBM_BW)
        ctl.timeline.call_at(kill_t, lambda t: ctl.transport.kill(1))
        ctl.run()
        assert len(q.completed) == 20, kill_t
        assert ctl.prefill_live == 0, kill_t
        # prefill spans stay serialized even after the failover
        prefills = sorted((s.t0, s.t1) for s in ctl.trace
                          if s.phase == "prefill" and s.t0 > kill_t)
        for (a0, a1), (b0, b1) in zip(prefills, prefills[1:]):
            assert b0 >= a1 - 1e-18, (kill_t, a0, a1, b0, b1)


def test_all_workers_dead_raises():
    q = RequestQueue()
    _load(q, 8)
    ctl = make_cluster(_specs(1), q, transport="loopback",
                       router="round_robin", bandwidth=hw.TPU_HBM_BW)
    ctl.timeline.call_at(1e-9, lambda t: ctl.transport.kill(0))
    with pytest.raises(ClusterError, match="unserved"):
        ctl.run()


def test_worker_error_propagates():
    """An engine contract violation inside a worker surfaces as a
    ClusterError, not a silent failover (the op would fail anywhere)."""
    q = RequestQueue()
    _load(q, 2, prompt_len=200)  # needs > max_len cache positions
    with pytest.raises(ClusterError, match="cache positions"):
        make_cluster(_specs(1, max_len=64), q, transport="loopback",
                     router="round_robin", bandwidth=hw.TPU_HBM_BW).run()


# ---------------------------------------------------------------------------
# the real process boundary (mp pipes and TCP sockets)
# ---------------------------------------------------------------------------

REMOTE_TRANSPORTS = ("mp", "socket")


@pytest.mark.parametrize("transport", REMOTE_TRANSPORTS)
@pytest.mark.parametrize("router", ["round_robin", "shaping"])
def test_remote_cluster_matches_loopback(transport, router):
    """The remote transports are the same protocol over real processes:
    pipe or TCP framing must not perturb the virtual clock — metrics must
    equal the loopback run's exactly, for every router."""
    q_lb = RequestQueue()
    _load(q_lb, 16, gen=4)
    m_lb = make_cluster(_specs(4), q_lb, transport="loopback",
                        router=router, bandwidth=hw.TPU_HBM_BW).run()
    q_rm = RequestQueue()
    _load(q_rm, 16, gen=4)
    m_rm = make_cluster(_specs(4), q_rm, transport=transport, router=router,
                        bandwidth=hw.TPU_HBM_BW,
                        heartbeat_timeout=120.0).run()
    assert len(q_rm.completed) == 16
    assert _stamps(q_rm) == _stamps(q_lb)
    assert _summary_no_wall(m_rm) == _summary_no_wall(m_lb)


@pytest.mark.parametrize("transport", REMOTE_TRANSPORTS)
def test_remote_worker_hard_kill_requeues_and_completes(transport):
    """The acceptance gate over real processes: SIGKILL one worker process
    mid-run; pipe/socket EOF marks it dead, its requests fail over, the
    run completes with no lost requests."""
    q = RequestQueue()
    _load(q, 18, gen=5)
    ctl = make_cluster(_specs(3), q, transport=transport,
                       router="round_robin", bandwidth=hw.TPU_HBM_BW,
                       heartbeat_timeout=120.0)
    ctl.timeline.call_at(1e-7, lambda t: ctl.transport.kill(2))
    ctl.run()
    assert ctl.n_failovers == 1 and ctl.failed_workers == [2]
    assert q.n_requeued > 0
    assert len(q.completed) == 18
    assert all(len(r.tokens) == r.max_new_tokens for r in q.completed)


# ---------------------------------------------------------------------------
# heartbeat
# ---------------------------------------------------------------------------


def test_heartbeat_pings_and_detects_death():
    q = RequestQueue()
    ctl = make_cluster(_specs(3), q, transport="loopback",
                       router="round_robin", bandwidth=hw.TPU_HBM_BW)
    assert ctl.heartbeat() == {0: True, 1: True, 2: True}
    ctl.transport.kill(1)
    assert ctl.heartbeat() == {0: True, 1: False, 2: True}
    assert ctl.failed_workers == [1]


def test_loopback_transport_is_strict_request_reply():
    tp = LoopbackTransport(_specs(1))
    assert isinstance(tp.recv(0), P.Hello)
    with pytest.raises(RuntimeError, match="request/reply"):
        tp.recv(0)


def test_real_replicas_each_hold_their_own_device():
    """Worker ``wid``'s real engine keeps its params and KV pages on local
    device ``wid % n`` for the whole run (four host devices, in a child so
    the device count does not leak into this process)."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.serving import RequestQueue, make_cluster, make_worker_specs
        q = RequestQueue()
        rng = np.random.default_rng(0)
        for _ in range(10):
            q.submit(rng.integers(1, 100, size=(8,)).astype(np.int32), 4)
        specs = make_worker_specs("qwen2-7b", 5, slots=2, max_len=32,
                                  engine="real")
        ctl = make_cluster(specs, q, transport="loopback")
        ctl.run()
        for wid, rt in sorted(ctl.transport.runtimes.items()):
            leaves = jax.tree.leaves((rt.engine.params, rt.engine.pages))
            print(wid, sorted({d.id for a in leaves for d in a.devices()}))
        print("completed", len(q.completed))
    """)
    src = Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, PYTHONPATH=str(src), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split("\n")[:6] == [
        "0 [0]", "1 [1]", "2 [2]", "3 [3]", "4 [0]", "completed 10"]
