"""Prefill/decode disaggregation: KV handoff + the PD router.

Pins the PR's acceptance gates:
  * wire completeness — ``KvHandoff`` (and the four PD messages around
    it) encode/decode round-trip through plain primitives, including the
    empty-page simulated payload and multi-layer bfloat16 caches;
  * engine handoff semantics — ``export_kv`` frees the slot and its
    blocks immediately; ``import_kv`` is all-or-nothing (a
    ``PoolExhausted`` leaves the destination engine untouched — the
    deferral path) and rejects contracts the engine could never serve;
  * phase purity — a PD-routed loopback cluster keeps its prefill pool
    decode-free and its decode pool prefill-free while completing the
    whole load, with every handoff priced as a ``"handoff"`` span on the
    shared contention timeline (and the same over the mp transport);
  * failover — killing the entire decode pool while handoffs are in
    flight re-queues those requests losslessly in admission (rid) order
    with their progress reset, and the surviving prefill workers absorb
    decode (degenerate co-located mode) so nothing is lost;
  * the oracle — a request prefilled on one real ``PartitionEngine``,
    exported, round-tripped through the wire codec, and imported into a
    second engine decodes BIT-IDENTICAL logits to the never-migrated
    engine, on both the paged and dense KV layouts.
"""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import hw
from repro.serving import (PartitionEngine, PdRouter, PoolExhausted,
                           RequestQueue, SimulatedEngine, make_cluster,
                           make_worker_specs)
from repro.serving.cluster import protocol as P
from repro.serving.pd import apply_handoff, export_handoff
from repro.serving.pd.handoff import handoff_request

ARCH = "qwen2-7b"


def _cfg():
    return get_config(ARCH, smoke=True)


def _load(queue, n, prompt_len=8, gen=4, deadline=None):
    rng = np.random.default_rng(0)
    return [queue.submit(rng.integers(1, 100, size=(prompt_len,))
                         .astype(np.int32), gen, deadline=deadline)
            for _ in range(n)]


def _sim(cfg, slots=2, max_len=32, pid=0, **kw):
    return SimulatedEngine(cfg, slots=slots, max_len=max_len, pid=pid,
                           peak_flops=hw.TPU_PEAK_FLOPS, block_size=8,
                           **kw)


def _specs(n, slots=2, max_len=64):
    return make_worker_specs(ARCH, n, slots=slots, max_len=max_len)


def _status():
    return P.WorkerStatus(busy=True, wants_prefill=False, backlog_len=1,
                          n_active=2, head_arrival=0.5, pre_dur=1e-6,
                          wave_dur=5e-6, active_rids=(3, 7))


# ---------------------------------------------------------------------------
# wire: KvHandoff serialization round-trips
# ---------------------------------------------------------------------------


def test_empty_page_handoff_round_trips():
    """A SimulatedEngine's handoff (no device arrays) survives the codec:
    request identity, generation progress, and transfer size intact."""
    cfg = _cfg()
    q = RequestQueue()
    reqs = _load(q, 1)
    eng = _sim(cfg)
    eng.assign(q.pop(1))
    eng.prefill_wave(0.0)
    h = export_handoff(eng, reqs[0].rid)
    assert h.pages == ()
    assert h.len > 0 and h.kv_bytes > 0
    for msg in (P.ImportKv(handoff=h),
                P.KvExported(handoffs=(h,), status=_status())):
        assert P.decode(P.encode(msg)) == msg


def test_pd_messages_round_trip():
    """Every PD message — including tuple-of-int and nested-dataclass
    fields — decodes back to an equal object from plain primitives."""
    h = P.KvHandoff(
        request=P.WireRequest(rid=4, prompt=(9, 8, 7), max_new_tokens=6,
                              arrival=0.25, deadline=2.0),
        tokens=(11, 12), t_first_token=0.5, len=5, kv_bytes=4096.0,
        pages=(P.pack_array("k", np.arange(12, dtype=np.float32)
                            .reshape(3, 4)),))
    msgs = [P.ExportKv(rids=(3, 7)),
            P.ImportKv(handoff=h),
            P.KvExported(handoffs=(h, h), status=_status()),
            P.KvImported(ok=False, reason="pool", status=_status())]
    for msg in msgs:
        d = P.encode(msg)
        assert isinstance(d, dict) and d["kind"] == type(msg).__name__
        assert P.decode(d) == msg
    # and the status round-trip keeps the PD migration field
    st = P.decode(P.encode(P.Pong(t_wall=1.0, status=_status()))).status
    assert st.active_rids == (3, 7)


def test_multilayer_bf16_pages_round_trip():
    """A real multi-layer cache payload: per-layer bfloat16 K/V blocks and
    float32 ssm rows reconstruct exactly (dtype, shape, bits)."""
    import ml_dtypes

    rng = np.random.default_rng(3)
    arrs = {
        "k": rng.standard_normal((2, 3, 8, 2, 16)).astype(ml_dtypes.bfloat16),
        "v": rng.standard_normal((2, 3, 8, 2, 16)).astype(ml_dtypes.bfloat16),
        "ssm_state": rng.standard_normal((2, 4, 4)).astype(np.float32),
    }
    h = P.KvHandoff(
        request=P.WireRequest(rid=1, prompt=(1, 2), max_new_tokens=4),
        tokens=(5,), t_first_token=1e-6, len=3, kv_bytes=1.0,
        pages=tuple(P.pack_array(n, a) for n, a in sorted(arrs.items())))
    h2 = P.decode(P.encode(P.ImportKv(handoff=h))).handoff
    assert h2 == h
    for pa in h2.pages:
        back = P.unpack_array(pa)
        assert back.dtype == arrs[pa.name].dtype
        assert back.shape == arrs[pa.name].shape
        assert back.tobytes() == arrs[pa.name].tobytes()
        back[(0,) * back.ndim] = 0  # unpack must hand back writable memory


# ---------------------------------------------------------------------------
# engine: export frees, import is all-or-nothing
# ---------------------------------------------------------------------------


def test_export_frees_slot_and_import_resumes_decode():
    cfg = _cfg()
    q = RequestQueue()
    reqs = _load(q, 2, prompt_len=8, gen=4)
    src = _sim(cfg, pid=0)
    src.assign(q.pop(2))
    src.prefill_wave(0.0)
    live0 = src.pool.n_live
    req, state = src.export_kv(reqs[0].rid)

    assert req.rid == reqs[0].rid and len(req.tokens) == 1
    assert state["pages"] == {}
    assert state["len"] == reqs[0].prompt_len  # first token's KV not yet written
    assert state["kv_bytes"] > 0
    assert src.n_exports == 1
    assert src.active[0] is None and src.slot_lens[0] == 0
    assert src.slot_tables[0] == [] and src.pool.n_live < live0
    with pytest.raises(KeyError, match="not active"):
        src.export_kv(999)

    dst = _sim(cfg, pid=1)
    slot = dst.import_kv(req, state)
    assert slot == 0 and dst.n_imports == 1
    assert dst.active[0] is req and dst.slot_lens[0] == state["len"]
    assert dst.assign_order == [req.rid]
    while dst.busy:
        dst.decode_step(0.0)
    done = {r.rid: r for r in dst.completed}
    assert len(done[req.rid].tokens) == req.max_new_tokens


def test_import_all_or_nothing_on_exhaustion():
    """No free slot, or not enough blocks: ``PoolExhausted`` before any
    mutation — the deferral contract the PD router retries on."""
    cfg = _cfg()
    q = RequestQueue()
    reqs = _load(q, 3, prompt_len=8, gen=4)
    src = _sim(cfg, slots=3, max_len=32)
    src.assign(q.pop(3))
    src.prefill_wave(0.0)
    _, state = src.export_kv(reqs[0].rid)

    # destination 1: every slot already taken (seated via import itself)
    full = _sim(cfg, slots=2)
    full.import_kv(*src.export_kv(reqs[1].rid))
    full.import_kv(*src.export_kv(reqs[2].rid))
    with pytest.raises(PoolExhausted, match="no free slot"):
        full.import_kv(reqs[0], state)
    assert full.n_imports == 2

    # destination 2: a free slot but a pool too small for the context
    tiny = _sim(cfg, slots=2, pool_blocks=1)
    free0 = tiny.pool.n_free
    with pytest.raises(PoolExhausted, match="blocks"):
        tiny.import_kv(reqs[0], state)
    assert tiny.n_imports == 0 and tiny.pool.n_free == free0
    assert tiny.active == [None, None] and tiny.assign_order == []

    # contract violations are errors, not deferrals
    with pytest.raises(ValueError, match="cache positions"):
        _sim(cfg, max_len=8).import_kv(reqs[0], state)
    with pytest.raises(ValueError, match="beyond its"):
        tiny.import_kv(reqs[0], dict(state, len=1000))


# ---------------------------------------------------------------------------
# cluster: phase-pure pools, handoff spans on the clock
# ---------------------------------------------------------------------------


def test_pd_loopback_pools_stay_phase_pure():
    q = RequestQueue()
    _load(q, 24, gen=4)
    ctl = make_cluster(_specs(4), q, transport="loopback",
                       router=PdRouter(split=(2, 2)),
                       bandwidth=hw.TPU_HBM_BW)
    ctl.run()
    assert len(q.completed) == 24
    assert all(len(r.tokens) == r.max_new_tokens for r in q.completed)
    assert all(r.t_first_token is not None for r in q.completed)
    eng = {w: ctl.transport.runtimes[w].engine for w in ctl.views}
    for w in (0, 1):   # prefill pool: never decodes, exports everything
        assert eng[w].n_prefills > 0 and eng[w].n_exports > 0
        assert eng[w].n_decode_steps == 0
    for w in (2, 3):   # decode pool: never prefills, imports its work
        assert eng[w].n_decode_steps > 0 and eng[w].n_imports > 0
        assert eng[w].n_prefills == 0
    r = ctl.router
    assert r.n_handoffs == sum(eng[w].n_exports for w in (0, 1)) == 24
    assert r.n_handoffs == sum(eng[w].n_imports for w in (2, 3)) \
        + r.n_requeued
    # every transfer ran as a bytes-only span on the contention clock
    spans = [s for s in ctl.trace if s.phase == "handoff"]
    assert len(spans) == r.n_handoffs
    assert all(s.demand > 0 and s.t1 > s.t0 for s in spans)
    assert {s.pid for s in spans} == {0, 1}  # billed at the source worker


def test_pd_mp_matches_loopback():
    """PD over real worker processes: identical protocol, identical
    virtual-clock stamps."""
    def run(transport, **kw):
        q = RequestQueue()
        _load(q, 12, gen=4)
        ctl = make_cluster(_specs(4), q, transport=transport,
                           router=PdRouter(split=(2, 2)),
                           bandwidth=hw.TPU_HBM_BW, **kw)
        ctl.run()
        assert len(q.completed) == 12
        return sorted((r.rid, r.t_first_token, r.t_done)
                      for r in q.completed)
    assert run("mp", heartbeat_timeout=120.0) == run("loopback")


def test_pd_split_must_cover_fleet():
    q = RequestQueue()
    _load(q, 4)
    ctl = make_cluster(_specs(3), q, transport="loopback",
                       router=PdRouter(split=(2, 2)),
                       bandwidth=hw.TPU_HBM_BW)
    with pytest.raises(ValueError, match="does not cover"):
        ctl.run()


# ---------------------------------------------------------------------------
# failover: decode pool dies under in-flight handoffs
# ---------------------------------------------------------------------------


def test_decode_pool_death_requeues_inflight_handoffs_in_order():
    """Kill the only decode worker while KV payloads are on the wire
    (handoff_rate makes the transfers outlast the kill): every in-flight
    request is re-queued with its progress reset, the queue re-sorts by
    rid (lossless admission order), and the surviving prefill workers
    finish the load co-located."""
    q = RequestQueue()
    _load(q, 8, gen=4)
    requeues = []
    orig = q.requeue

    def spy(reqs):
        requeues.append([(r.rid, list(r.tokens), r.t_first_token)
                         for r in reqs])
        orig(reqs)
        assert [r.rid for r in q._fifo] == \
            sorted(r.rid for r in q._fifo)  # the admission-order invariant

    q.requeue = spy
    router = PdRouter(split=(2, 1), handoff_rate=1.0)  # ~kB payloads: hours
    ctl = make_cluster(_specs(3), q, transport="loopback", router=router,
                       bandwidth=hw.TPU_HBM_BW)
    ctl.timeline.call_at(1.0, lambda t: ctl.transport.kill(2))
    ctl.run()

    assert ctl.n_failovers == 1 and ctl.failed_workers == [2]
    assert router.n_requeued > 0          # in-flight handoffs came back
    assert router._in_flight == 0 and not router._deferred
    pd_calls = [c for c in requeues if len(c) == 1]  # one per transfer
    assert len(pd_calls) >= router.n_requeued
    for call in pd_calls:
        _, tokens, t_first = call[0]
        assert tokens == [] and t_first is None  # progress reset: lossless
    # nothing lost: the whole load completes on the survivors
    assert len(q.completed) == 8
    assert all(len(r.tokens) == r.max_new_tokens for r in q.completed)
    assert all(r.arrival == 0.0 for r in q.completed)
    # the survivors really did absorb decode (degenerate co-located mode)
    eng = {w: ctl.transport.runtimes[w].engine for w in (0, 1)}
    assert sum(e.n_decode_steps for e in eng.values()) > 0


# ---------------------------------------------------------------------------
# the oracle: migrated decode is bit-identical to never migrating
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def built():
    import jax
    from repro.models import api as mapi

    # float32 so the bit-identity claim is about cache state, not rounding
    cfg = get_config(ARCH, smoke=True).replace(dtype="float32")
    m = mapi.build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _engine(cfg, m, params, paged):
    return PartitionEngine(cfg, m, params, slots=2, max_len=48,
                           peak_flops=hw.TPU_PEAK_FLOPS, paged=paged,
                           block_size=8)


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_migrated_decode_is_bit_identical_to_oracle(built, paged):
    """Prefill on engine A, export, full wire round-trip, import into
    engine B; B's every decode logit equals the never-migrated oracle's
    EXACTLY (np.array_equal, no tolerance), and so do the tokens."""
    cfg, m, params = built
    lens = [8, 12]
    qa, qo = RequestQueue(), RequestQueue()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab, size=(l,)).astype(np.int32)
               for l in lens]
    for p in prompts:
        qa.submit(p, 4)
        qo.submit(p, 4)

    src = _engine(cfg, m, params, paged)
    oracle = _engine(cfg, m, params, paged)
    src.assign(qa.pop(2))
    oracle.assign(qo.pop(2))
    src.prefill_wave(0.0)
    oracle.prefill_wave(0.0)

    dst = _engine(cfg, m, params, paged)
    for req in [r for r in list(src.active) if r is not None]:
        h = export_handoff(src, req.rid)
        assert h.pages and {pa.name for pa in h.pages} >= {"k", "v"}
        h2 = P.decode(P.encode(P.ImportKv(handoff=h))).handoff  # full wire
        assert h2 == h
        apply_handoff(dst, h2)
    assert src.n_exports == 2 and dst.n_imports == 2
    assert not src.busy and dst.busy

    steps = 0
    while oracle.busy:
        assert dst.busy
        mask = [r is not None for r in oracle.active]
        dst.decode_step(0.0)
        oracle.decode_step(0.0)
        for i, was_active in enumerate(mask):
            if was_active:
                assert np.array_equal(np.asarray(dst.last_logits[i]),
                                      np.asarray(oracle.last_logits[i]))
        steps += 1
    assert steps > 0 and not dst.busy
    for rm, ro in zip(sorted(dst.completed, key=lambda r: r.rid),
                      sorted(oracle.completed, key=lambda r: r.rid)):
        assert rm.rid == ro.rid and rm.tokens == ro.tokens
    if paged:
        assert dst.pool.n_live == 0  # imported blocks fully returned


def test_handoff_request_restores_progress(built):
    cfg, m, params = built
    q = RequestQueue()
    q.submit(np.arange(1, 9, dtype=np.int32), 4)
    eng = _engine(cfg, m, params, True)
    eng.assign(q.pop(1))
    eng.prefill_wave(2.5e-6)
    h = export_handoff(eng, eng.assign_order[0])
    req = handoff_request(h)
    assert req.tokens == list(h.tokens) and len(req.tokens) == 1
    assert req.t_first_token == h.t_first_token is not None


# ---------------------------------------------------------------------------
# CLI validation (parse-time, shared by cluster.py and serve.py)
# ---------------------------------------------------------------------------


def _cluster_main(extra):
    from repro.launch.cluster import main
    main(["--arch", ARCH, "--smoke"] + extra)


@pytest.mark.parametrize("extra", [
    ["--heartbeat-timeout", "0"],
    ["--heartbeat-timeout", "-3"],
    ["--pd-split", "2:2"],                       # needs --router pd
    ["--router", "pd", "--pd-split", "nope"],
    ["--router", "pd", "--pd-split", "4"],
    ["--router", "pd", "--pd-split", "0:4"],
    ["--router", "pd", "--pd-split", "2:3"],     # 4-worker default fleet
], ids=["hb-zero", "hb-neg", "split-sans-pd", "split-garbage",
        "split-one-int", "split-empty-pool", "split-mismatch"])
def test_cluster_cli_rejects_bad_flags(extra):
    with pytest.raises(SystemExit):
        _cluster_main(extra)


@pytest.mark.parametrize("transport", ["mp", "socket"])
def test_cluster_cli_refuses_real_engines_over_processes(transport, capsys):
    """mp/socket start one process per worker; real engines (no
    --simulated) would each load the TPU, so the CLI points to loopback."""
    with pytest.raises(SystemExit):
        _cluster_main(["--transport", transport])
    assert "--transport loopback" in capsys.readouterr().err
    _cluster_main(["--transport", transport, "--simulated", "--workers",
                   "2", "--requests", "2", "--heartbeat-timeout", "120"])


def test_serve_cli_rejects_pd_without_cluster():
    from repro.launch.serve import main
    with pytest.raises(SystemExit):
        main(["--arch", ARCH, "--smoke", "--router", "pd"])
    with pytest.raises(SystemExit):
        main(["--arch", ARCH, "--smoke", "--cluster", "4",
              "--router", "pd", "--pd-split", "1:2"])
