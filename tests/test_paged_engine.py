"""Engine-equivalence suite for the paged KV-cache pool.

The paged ``PartitionEngine`` (block-table pool + ``decode_step_paged``)
must serve EXACTLY what the dense per-slot oracle serves: same greedy
tokens, same logits within fp tolerance, on identical ragged token streams
with mid-wave slot refills.  Plus the serving-level gates: a mixed
prompt-length wave serves end-to-end (the seed engine raised ValueError),
and pool exhaustion defers seating instead of truncating context.
"""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import hw
from repro.serving import (PartitionEngine, PhaseStaggeredScheduler,
                           RequestQueue, SimulatedEngine)

LENS = [8, 12, 10, 8, 12]  # ragged wave + enough backlog to force refills


@pytest.fixture(scope="module")
def built():
    import jax
    from repro.models import api as mapi

    # float32 so paged/dense argmax never diverges on bf16 rounding
    cfg = get_config("qwen2-7b", smoke=True).replace(dtype="float32")
    m = mapi.build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _load(queue, lens, gen=4, vocab=256):
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, vocab, size=(l,)).astype(np.int32)
               for l in lens]
    return [queue.submit(p, gen) for p in prompts]


def _engine(cfg, m, params, paged):
    return PartitionEngine(cfg, m, params, slots=2, max_len=48,
                           peak_flops=hw.TPU_PEAK_FLOPS, paged=paged,
                           block_size=8)


def test_paged_decode_logits_match_dense_oracle(built):
    """Lockstep drive of a paged and a dense engine on identical ragged
    request streams: identical slot occupancy, identical greedy tokens,
    logits equal within fp tolerance at every decode step."""
    cfg, m, params = built
    qp, qd = RequestQueue(), RequestQueue()
    _load(qp, LENS, vocab=cfg.vocab)
    _load(qd, LENS, vocab=cfg.vocab)
    ep, ed = _engine(cfg, m, params, True), _engine(cfg, m, params, False)
    ep.assign(qp.pop(len(LENS)))
    ed.assign(qd.pop(len(LENS)))

    ep.prefill_wave(0.0)
    ed.prefill_wave(0.0)
    steps = 0
    while ed.busy:
        assert ep.busy
        mask = [r is not None for r in ed.active]
        ep.decode_step(0.0)
        ed.decode_step(0.0)
        for i, was_active in enumerate(mask):
            if was_active:
                np.testing.assert_allclose(
                    ep.last_logits[i], ed.last_logits[i],
                    rtol=2e-4, atol=2e-4)
        steps += 1
    assert not ep.busy
    assert steps > 0 and ep.n_refills == ed.n_refills > 0
    for rp, rd in zip(sorted(ep.completed, key=lambda r: r.rid),
                      sorted(ed.completed, key=lambda r: r.rid)):
        assert rp.rid == rd.rid and rp.tokens == rd.tokens
    assert ep.slot_tokens == ed.slot_tokens
    assert ep.assign_order == ed.assign_order == sorted(ep.assign_order)
    assert ep.pool.n_live == 0  # every block returned to the pool


def test_request_seated_into_a_never_used_slot_decodes_as_alone(built):
    """A request assigned while the engine decodes another is seated into
    a slot no request has used (batch-1 refill at the next commit).  It
    gets exactly the greedy tokens it gets when served alone, and its
    logits row at each decode step matches the lone run's."""
    cfg, m, params = built
    rng = np.random.default_rng(11)
    p0, p1 = (rng.integers(1, cfg.vocab, size=(n,)).astype(np.int32)
              for n in (8, 12))

    shared = _engine(cfg, m, params, True)
    q = RequestQueue()
    q.submit(p0, 6)
    shared.assign(q.pop(1))
    shared.prefill_wave(0.0)
    assert shared.active[1] is None and shared.slot_tables[1] == []
    late = q.submit(p1, 4)
    shared.assign(q.pop(1))
    shared.decode_step(1.0)              # its commit seats ``late``
    assert shared.active[1] is late and shared.n_free_seats == 1
    rows = []
    while not late.done:
        assert shared.active[0] is not None   # decoding beside the other
        shared.decode_step(2.0)
        rows.append(shared.last_logits[1].copy())

    alone = _engine(cfg, m, params, True)
    q = RequestQueue()
    solo = q.submit(p1, 4)
    alone.assign(q.pop(1))
    alone.prefill_wave(0.0)
    lone_rows = []
    while alone.busy:
        alone.decode_step(1.0)
        lone_rows.append(alone.last_logits[0].copy())

    assert late.tokens == solo.tokens and len(rows) == len(lone_rows) == 3
    for a, b in zip(rows, lone_rows):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
def test_decode_step_appends_one_row_per_active_slot(kv_dtype):
    """One decode step changes the pool only where it appends: in each
    layer, the one row at each active slot's write position inside its
    current block (and the null block, which the idle slot writes).  Every
    other row and block is bit-identical.  A bf16 pool keeps the current
    block's other rows bit-identical too; an int8 pool requantizes the
    whole block with a fresh scale, so they move by at most half of it."""
    import jax
    from repro.models import api as mapi
    from repro.serving.kv_pool import NULL_BLOCK, dequantize_kv

    cfg = get_config("qwen2-7b", smoke=True)           # bf16 weights and KV
    m = mapi.build(cfg)
    params = m.init(jax.random.PRNGKey(1))
    bs = 8
    eng = PartitionEngine(cfg, m, params, slots=3, max_len=48,
                          peak_flops=hw.TPU_PEAK_FLOPS, paged=True,
                          block_size=bs, kv_dtype=kv_dtype)
    q = RequestQueue()
    _load(q, [8, 12], vocab=cfg.vocab)      # one block start, one mid-block
    eng.assign(q.pop(2))
    eng.prefill_wave(0.0)
    before = {k: np.asarray(v) for k, v in eng.pages.items()}
    rows = {}                               # current block -> written row
    for i in range(2):
        pos = eng.slot_lens[i]
        rows[eng.slot_tables[i][pos // bs]] = pos % bs
    assert sorted(rows.values()) == [0, 4]
    eng.decode_step(0.0)
    after = {k: np.asarray(v) for k, v in eng.pages.items()}

    untouched = np.ones(before["k_pages"].shape[1], bool)
    untouched[list(rows) + [NULL_BLOCK]] = False
    for name in ("k", "v"):
        pb, pa = before[f"{name}_pages"], after[f"{name}_pages"]
        np.testing.assert_array_equal(pa[:, untouched], pb[:, untouched])
        for blk, r in rows.items():
            other = np.arange(bs) != r
            # (L, Hkv, D): the new row differs in every layer
            assert (pa[:, blk, :, r] != pb[:, blk, :, r]).any(axis=(1, 2)).all()
            if kv_dtype == "fp32":
                np.testing.assert_array_equal(pa[:, blk][:, :, other],
                                              pb[:, blk][:, :, other])
                continue
            sb = before[f"{name}_scales"][:, blk]            # (L, Hkv)
            sa = after[f"{name}_scales"][:, blk]
            np.testing.assert_array_equal(
                after[f"{name}_scales"][:, untouched],
                before[f"{name}_scales"][:, untouched])
            old = np.asarray(dequantize_kv(pb[:, blk], sb))[:, :, other]
            new = np.asarray(dequantize_kv(pa[:, blk], sa))[:, :, other]
            bound = sa[:, :, None, None] * (0.5 + 1e-5) + 1e-9
            assert (np.abs(new - old) <= bound).all()


def test_mixed_length_wave_serves_instead_of_raising():
    """The seed engine raised ``ValueError: mixed prompt lengths in one
    prefill wave``; per-slot lengths make the same load a normal wave."""
    cfg = get_config("qwen2-7b", smoke=True)
    q = RequestQueue()
    lens = [16, 24, 32, 16, 24, 32, 16, 24]
    _load(q, lens, vocab=cfg.vocab)
    engines = [SimulatedEngine(cfg, slots=3, max_len=64, pid=p,
                               peak_flops=hw.TPU_PEAK_FLOPS / 2)
               for p in range(2)]
    sched = PhaseStaggeredScheduler(engines, q, policy="demand")
    sched.run(max_ticks=2000)
    done = sorted(q.completed, key=lambda r: r.rid)
    assert len(done) == len(lens)
    assert all(len(r.tokens) == r.max_new_tokens for r in done)
    for eng in engines:  # FIFO service order preserved per partition
        assert eng.assign_order == sorted(eng.assign_order)
    # the ragged wave really was fused: one engine's first wave seated
    # more than one distinct prompt length (the seed's ValueError case)
    plen = {r.rid: r.prompt_len for r in done}
    ragged = any(len({plen[rid] for rid in eng.assign_order[:eng.slots]}) > 1
                 for eng in engines)
    assert ragged


def test_pool_exhaustion_defers_seating_not_context():
    """An undersized pool seats only what fits; the rest stays queued FIFO
    and serves after blocks are freed — nothing is truncated or dropped."""
    cfg = get_config("qwen2-7b", smoke=True)
    q = RequestQueue()
    _load(q, [8] * 6, gen=4, vocab=cfg.vocab)
    # per request: 8 + 4 = 12 tokens -> 2 blocks of 8; pool fits only 2
    eng = SimulatedEngine(cfg, slots=4, max_len=32,
                          peak_flops=hw.TPU_PEAK_FLOPS,
                          block_size=8, pool_blocks=5)
    max_seated = 0
    eng.assign(q.pop(6))
    now = 0.0
    for _ in range(200):
        if eng.wants_prefill:
            eng.prefill_wave(now)
        elif eng.busy:
            eng.decode_step(now)
        else:
            break
        max_seated = max(max_seated,
                         sum(r is not None for r in eng.active))
    assert len(eng.completed) == 6
    assert max_seated == 2          # pool capacity, not slot count, gated
    assert eng.assign_order == sorted(eng.assign_order)
    assert eng.pool.n_live == 0


def test_oversized_request_raises_without_leaking_blocks():
    """A request over the per-slot budget is a contract error — and the
    error path must not strand blocks already allocated for wave-mates."""
    cfg = get_config("qwen2-7b", smoke=True)
    q = RequestQueue()
    rng = np.random.default_rng(0)
    q.submit(rng.integers(1, 64, size=(8,)).astype(np.int32), 4)   # fits
    q.submit(rng.integers(1, 64, size=(40,)).astype(np.int32), 8)  # 48 > 32
    eng = SimulatedEngine(cfg, slots=2, max_len=32,
                          peak_flops=hw.TPU_PEAK_FLOPS, block_size=8)
    eng.assign(q.pop(2))
    with pytest.raises(ValueError):
        eng.prefill_wave(0.0)
    assert eng.pool.n_live == 0


def test_paged_partition_engine_serves_ragged_via_scheduler(built):
    """Full stack: paged real engine + scheduler + queue on a ragged load
    with continuous per-slot refill."""
    cfg, m, params = built
    q = RequestQueue()
    _load(q, LENS, vocab=cfg.vocab)
    eng = _engine(cfg, m, params, True)
    sched = PhaseStaggeredScheduler([eng], q, policy="none")
    m_out = sched.run(max_ticks=300)
    done = sorted(q.completed, key=lambda r: r.rid)
    assert len(done) == len(LENS)
    assert all(len(r.tokens) == r.max_new_tokens for r in done)
    assert eng.n_refills > 0
    assert m_out.completed_tokens == sum(r.max_new_tokens for r in done)
