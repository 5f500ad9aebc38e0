"""repro.obs: tracing, metrics registry, per-request lifecycles.

Pins the PR's acceptance gates:
  * determinism — two identical P=4 event-clock runs export byte-identical
    Chrome-trace JSON (virtual timestamps, canonical serialisation);
  * schema — exported traces pass ``validate_chrome`` (required fields,
    monotone timestamps, balanced begin/end per track, numeric counters,
    paired flows) and the validator actually catches corruption;
  * zero overhead when off — with ``tracer is None`` the hot
    issue/commit path allocates nothing in ``repro/obs`` (the guard is a
    plain attribute test, no tracing code runs);
  * cancellation accounting — ``ContentionTimeline.cancel`` records the
    forfeited partial progress unconditionally and, when tracing, emits a
    ``cancelled`` event carrying bytes-completed;
  * fidelity — the bw counter track integrated back out of a trace
    reproduces ``ServingMetrics.bw_stats`` to 1e-9 relative;
  * the cluster path — a traced loopback cluster (shaping and pd
    routers) produces a valid trace with paired handoff flows and a
    fleet registry aggregated from ``WorkerStatus`` snapshots.
"""
import json
import tracemalloc

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import hw
from repro.core.timeline import ContentionTimeline
from repro.obs import (MetricsRegistry, Tracer, merge_snapshots, to_chrome,
                       trace_bw_segments, validate_chrome, write_chrome)
from repro.serving import (RequestQueue, SimulatedEngine, make_cluster,
                           make_scheduler, make_worker_specs)
from repro.serving.trace_sim import phase_balanced_bandwidth


def _cfg():
    return get_config("qwen2-7b", smoke=True)


def _fleet(cfg, partitions, slots=2, max_len=64):
    return [SimulatedEngine(cfg, slots=slots, max_len=max_len, pid=p,
                            peak_flops=hw.TPU_PEAK_FLOPS / partitions)
            for p in range(partitions)]


def _load(queue, n, prompt_len=8, gen=4):
    rng = np.random.default_rng(0)
    for _ in range(n):
        queue.submit(rng.integers(1, 100, size=(prompt_len,))
                     .astype(np.int32), gen)


def _traced_run(policy="demand", partitions=4, n=10, trace_path=None):
    """One traced in-process event-clock run; returns (tracer, sched, m)."""
    cfg = _cfg()
    q = RequestQueue()
    tracer = Tracer()
    q.tracer = tracer  # before the load: admissions must be captured
    _load(q, n)
    bw = phase_balanced_bandwidth(cfg, total_slots=partitions * 2,
                                  prompt_len=8, gen=4)
    sched = make_scheduler(_fleet(cfg, partitions), q, policy=policy,
                           bandwidth=bw, clock="event")
    sched.attach_tracer(tracer)
    m = sched.run()
    if trace_path is not None:
        write_chrome(tracer, str(trace_path))
    return tracer, sched, m


# ---------------------------------------------------------------------------
# determinism + schema
# ---------------------------------------------------------------------------


def test_identical_runs_export_byte_identical_traces(tmp_path):
    paths = [tmp_path / "a.json", tmp_path / "b.json"]
    for p in paths:
        _traced_run(trace_path=p)
    a, b = (p.read_bytes() for p in paths)
    assert a == b and len(a) > 0


def test_exported_trace_passes_schema_validation(tmp_path):
    path = tmp_path / "t.json"
    _traced_run(trace_path=path)
    doc = json.loads(path.read_text())
    assert validate_chrome(doc) == []
    # the run's structure is actually in there: per-partition span tracks,
    # queue admissions, policy instants, the bw counter track
    evs = doc["traceEvents"]
    groups = {ev["args"]["name"] for ev in evs
              if ev["ph"] == "M" and ev["name"] == "process_name"}
    assert {"spans", "queue", "policy"} <= groups
    assert any(ev["ph"] == "C" and ev["name"] == "bw" for ev in evs)
    assert any(ev["ph"] == "B" and ev["name"] == "prefill" for ev in evs)
    assert any(ev["ph"] == "B" and ev["name"] == "decode" for ev in evs)


def test_validator_catches_corruption():
    tracer, _, _ = _traced_run(n=6)
    doc = to_chrome(tracer.events)
    assert validate_chrome(doc) == []
    # (a) an E dropped -> unbalanced stack
    evs = [e for e in doc["traceEvents"]]
    kill = next(i for i, e in enumerate(evs) if e["ph"] == "E")
    assert validate_chrome({"traceEvents": evs[:kill] + evs[kill + 1:]})
    # (b) a timestamp pushed backwards -> monotonicity violation
    evs2 = [dict(e) for e in doc["traceEvents"]]
    last = max(i for i, e in enumerate(evs2) if e["ph"] != "M")
    evs2[last]["ts"] = -1.0
    assert validate_chrome({"traceEvents": evs2})
    # (c) a non-numeric counter series
    bad = {"traceEvents": [{"name": "bw", "ph": "C", "ts": 0.0, "pid": 1,
                            "tid": 0, "args": {"demand": "oops"}}]}
    assert validate_chrome(bad)
    # (d) a flow finish with no start
    bad = {"traceEvents": [{"name": "x", "ph": "f", "ts": 0.0, "pid": 1,
                            "tid": 0, "id": 7, "args": {}}]}
    assert validate_chrome(bad)


# ---------------------------------------------------------------------------
# zero overhead when off
# ---------------------------------------------------------------------------


def test_tracer_off_hot_path_allocates_nothing_in_obs():
    """With every ``tracer`` attribute at None (the default), a full
    serving run must not execute a single line of ``repro/obs`` — pinned
    by tracemalloc: zero allocations attributed to the package."""
    import repro.obs  # ensure the package is imported; still never called
    cfg = _cfg()
    q = RequestQueue()
    _load(q, 8)
    sched = make_scheduler(_fleet(cfg, 2), q, policy="demand",
                           bandwidth=2e9, clock="event")
    assert sched.timeline.tracer is None and q.tracer is None
    tracemalloc.start()
    try:
        sched.run()
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    obs_allocs = [s for s in snap.statistics("filename")
                  if "repro/obs" in s.traceback[0].filename.replace(
                      "\\", "/")]
    assert obs_allocs == []
    assert len(q.completed) == 8


# ---------------------------------------------------------------------------
# cancellation accounting
# ---------------------------------------------------------------------------


def test_cancel_records_partial_progress_unconditionally():
    tl = ContentionTimeline(1e12)
    sp = tl.start(1.0, 1e9)
    tl.call_at(0.5, lambda t: tl.cancel(sp))
    tl.run()
    assert tl.n_cancelled == 1
    assert tl.cancelled_bytes == pytest.approx(0.5e9)
    assert tl.n_completed == 0


def test_cancel_emits_cancelled_event_with_bytes_done():
    tl = ContentionTimeline(1e12)
    tracer = Tracer()
    tl.attach_tracer(tracer)
    sp = tl.start(1.0, 1e9, key=(3, "prefill"))
    tl.call_at(0.5, lambda t: tl.cancel(sp))
    tl.run()
    cancels = [e for e in tracer.events
               if e["ph"] == "i" and e["name"] == "cancelled"]
    assert len(cancels) == 1
    assert cancels[0]["args"]["bytes_done"] == pytest.approx(0.5e9)
    ends = [e for e in tracer.events if e["ph"] == "E"]
    assert len(ends) == 1 and ends[0]["args"]["cancelled"] is True
    # the truncated slice still exports balanced
    assert validate_chrome(to_chrome(tracer.events)) == []


# ---------------------------------------------------------------------------
# lifecycle records
# ---------------------------------------------------------------------------


def test_lifecycle_stages_are_ordered_and_complete():
    tracer, _, _ = _traced_run(n=10)
    lc = tracer.lifecycle
    assert len(lc.records) == 10
    for rid, recs in lc.records.items():
        stages = [s for s, _, _ in recs]
        times = [t for _, t, _ in recs]
        assert stages[0] == "submit"
        assert stages[-1] == "retire"
        assert "prefill" in stages and "first_token" in stages
        assert stages.index("prefill") < stages.index("first_token")
        assert times == sorted(times)
    s = lc.summary()
    assert s["n_submit"] == s["n_retire"] == 10
    assert s["mean_submit_to_retire"] >= s["mean_submit_to_first_token"] > 0
    line = lc.format_exit_line()
    assert line.startswith("lifecycle: ") and "retire=10" in line


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_snapshot_merge_and_histogram():
    regs = []
    for k in range(2):
        r = MetricsRegistry()
        r.inc("prefix.hits", 3)
        r.set_gauge("pool.free_blocks", 10 + k)
        r.observe("phase.decode.duration", 1e-3)
        regs.append(r)
    merged = merge_snapshots(r.snapshot() for r in regs)
    assert merged.get("prefix.hits") == 6
    assert merged.get("pool.free_blocks") == 21  # gauges sum fleet-wide
    assert merged.get("phase.decode.duration.count") == 2
    assert merged.get("phase.decode.duration.sum") == pytest.approx(2e-3)
    # snapshots are sorted and deterministic
    assert regs[0].snapshot() == regs[0].snapshot()
    names = [k for k, _ in regs[0].snapshot()]
    assert names == sorted(names)


def test_engine_metrics_snapshot_feeds_fleet_registry():
    tracer, sched, _ = _traced_run(n=8, partitions=2)
    from repro.obs import registry_from_engines
    reg = registry_from_engines(sched.engines, queue=sched.queue)
    assert reg.get("engine.prefills") >= 2
    assert reg.get("engine.decode_steps") > 0
    assert reg.get("queue.submitted") == 8


# ---------------------------------------------------------------------------
# counter-track fidelity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ["none", "demand"])
def test_trace_bw_counter_reproduces_metrics_bw_stats(tmp_path, policy):
    """The demand counter track, integrated back out of the exported
    JSON, must reproduce the metrics overlay stats to 1e-9 relative —
    the trace IS the Fig. 6 curve, not an approximation of it."""
    path = tmp_path / f"{policy}.json"
    _, _, m = _traced_run(policy=policy, trace_path=path)
    doc = json.loads(path.read_text())
    segs = trace_bw_segments(doc)
    assert segs
    w = np.array([b - a for a, b, _ in segs])
    v = np.array([val for _, _, val in segs])
    mean = float(np.average(v, weights=w))
    std = float(np.sqrt(np.average((v - mean) ** 2, weights=w)))
    m_mean, m_std = m.bw_stats(0.0)
    assert mean == pytest.approx(m_mean, rel=1e-9)
    assert std == pytest.approx(m_std, rel=1e-9)


# ---------------------------------------------------------------------------
# the cluster path
# ---------------------------------------------------------------------------


def _traced_cluster(router, workers=2, n=8, **kw):
    cfg = _cfg()
    q = RequestQueue()
    tracer = Tracer()
    q.tracer = tracer
    _load(q, n)
    specs = make_worker_specs("qwen2-7b", workers, smoke=True, slots=2,
                              max_len=64, engine="sim", **kw)
    bw = phase_balanced_bandwidth(cfg, total_slots=workers * 2,
                                  prompt_len=8, gen=4)
    ctl = make_cluster(specs, q, transport="loopback", router=router,
                       bandwidth=bw)
    ctl.attach_tracer(tracer)
    ctl.run()
    return tracer, ctl


def test_cluster_trace_valid_and_fleet_registry_aggregates():
    tracer, ctl = _traced_cluster("shaping")
    doc = to_chrome(tracer.events)
    assert validate_chrome(doc) == []
    # dispatch instants on the cluster track group, spans per worker
    assert any(e["ph"] == "i" and e["name"] == "dispatch"
               for e in tracer.events)
    reg = ctl.fleet_registry()
    assert reg.get("engine.prefills") >= 2
    assert reg.get("pool.free_blocks") > 0
    lc = tracer.lifecycle
    assert lc.stage_counts()["dispatch"] == 8
    assert lc.stage_counts()["retire"] == 8


def test_pd_cluster_trace_pairs_handoff_flows():
    tracer, ctl = _traced_cluster("pd", workers=2, n=6)
    starts = [e for e in tracer.events if e["ph"] == "s"]
    ends = [e for e in tracer.events if e["ph"] == "f"]
    assert len(starts) == ctl.router.n_handoffs > 0
    assert len(ends) == len(starts)
    assert {e["id"] for e in starts} == {e["id"] for e in ends}
    assert validate_chrome(to_chrome(tracer.events)) == []
    counts = tracer.lifecycle.stage_counts()
    assert counts["handoff_export"] == counts["handoff_import"] == \
        ctl.router.n_handoffs


# ---------------------------------------------------------------------------
# clocks: the virtual trace as it was, the wall clock beside it
# ---------------------------------------------------------------------------


def _digest(tracer) -> tuple:
    import hashlib

    doc = json.dumps(to_chrome(tracer.events), sort_keys=True).encode()
    lc = json.dumps({str(k): v for k, v in
                     sorted(tracer.lifecycle.records.items())},
                    sort_keys=True).encode()
    return (hashlib.sha256(doc).hexdigest()[:16],
            hashlib.sha256(lc).hexdigest()[:16])


@pytest.mark.parametrize("run, want", [
    (lambda: _traced_run()[0],
     ("e457643c064587f8", "8a0780fbec12f24c")),
    (lambda: _traced_run(policy="none", partitions=1)[0],
     ("0215bff9031b3605", "8e3d73a3b6e51b42")),
    (lambda: _traced_cluster("pd", workers=2, n=6)[0],
     ("45e1ecccc5bedefb", None)),
])
def test_virtual_trace_matches_its_pinned_digest(run, want):
    """Host spans and wall-clock stamps leave a virtual-clock trace byte
    for byte as it was before them (digests of the export and of the
    lifecycle records, recorded before the host spans existed)."""
    got = _digest(run())
    assert got[0] == want[0]
    if want[1] is not None:
        assert got[1] == want[1]


def test_wall_tracer_keeps_its_clock_and_stamps_on_emission(tmp_path):
    import time

    from repro.obs import WallClock

    cfg = _cfg()
    q = RequestQueue()
    tracer = Tracer(clock=WallClock())
    sched = make_scheduler(_fleet(cfg, 2), q, policy="demand",
                           bandwidth=2e9, clock="event")
    sched.attach_tracer(tracer)
    assert isinstance(tracer.clock, WallClock)
    t0 = time.perf_counter()
    _load(q, 6)
    sched.run()
    t1 = time.perf_counter()
    ts = [ev["t"] for ev in tracer.events]
    assert ts == sorted(ts) and t0 <= ts[0] and ts[-1] <= t1
    names = {ev["name"] for ev in tracer.events if ev["group"] == "host"}
    assert {"scheduler.step", "engine.decode", "engine.decode.price",
            "engine.decode.bookkeeping", "engine.prefill",
            "engine.commit"} <= names
    for recs in tracer.lifecycle.records.values():
        stamp = {s: t for s, t, _ in reversed(recs)}
        assert t0 <= stamp["submit"] <= stamp["prefill"] \
            <= stamp["first_token"] <= stamp["retire"] <= t1
    assert validate_chrome(to_chrome(tracer.events)) == []


def test_span_is_a_noop_on_the_virtual_clock():
    from repro.obs.tracer import NO_SPAN

    tracer = Tracer()
    with tracer.span("engine.decode", kv_blocks_live=3) as sp:
        pass
    assert tracer.span("x") is NO_SPAN and sp is None
    assert tracer.events == [] and tracer.open_spans == []


def test_wall_span_nests_records_and_annotates(monkeypatch):
    from repro.obs import WallClock

    tracer = Tracer(clock=WallClock())
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            entered.append("/" + self.name)

    tracer._annotation = Annotation
    with tracer.span("engine.decode", tid=2, kv_blocks_live=5):
        assert tracer.open_spans == ["engine.decode"]
        with tracer.span("engine.decode.launch", tid=2):
            assert tracer.open_spans[-1] == "engine.decode.launch"
    assert tracer.open_spans == []
    assert entered == ["engine.decode", "engine.decode.launch",
                       "/engine.decode.launch", "/engine.decode"]
    assert [(e["ph"], e["name"]) for e in tracer.events] == [
        ("B", "engine.decode"), ("B", "engine.decode.launch"),
        ("E", "engine.decode.launch"), ("E", "engine.decode")]
    assert tracer.events[0]["args"] == {"kv_blocks_live": 5}
    assert all(e["group"] == "host" and e["tid"] == 2
               for e in tracer.events)


def test_kv_block_counts_by_hand():
    """Addressed blocks are every slot's whole table, inactive rows too;
    live blocks cover each active slot's context plus the new token."""
    from repro.obs import WallClock
    from repro.serving.queue import Request

    eng = SimulatedEngine(_cfg(), slots=4, max_len=64, block_size=16)
    assert eng.table_width == 4
    reqs = [Request(rid=k, prompt=np.ones(4, np.int32), max_new_tokens=9)
            for k in range(3)]
    for i, (req, n) in enumerate(zip(reqs, [15, 16, 40])):
        eng.active[i + (i > 0)] = req
        eng.slot_lens[i + (i > 0)] = n
    # slots: 0 -> 15 (16 positions, 1 block), 1 idle, 2 -> 16 (17, 2
    # blocks), 3 -> 40 (41, 3 blocks)
    assert eng.kv_blocks_read() == (16, 6)
    eng.tracer = Tracer(clock=WallClock())
    eng.issue_decode()
    b = next(e for e in eng.tracer.events
             if e["ph"] == "B" and e["name"] == "engine.decode")
    assert b["args"] == {"kv_blocks_addressed": 16, "kv_blocks_live": 6}
