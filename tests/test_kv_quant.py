"""Quantized KV cache + blockwise-sparse decode through the serving stack.

Pins the PR's acceptance gates:
  * equivalence — an int8-quantized paged engine serves the reference
    stream with logits inside the documented quantization budget of the
    fp32 paged engine (and identical greedy tokens on this stream); a
    small sparse threshold that drops nothing reproduces dense serving
    within base fp tolerance;
  * loud refusal — unknown dtypes, an unsupported fp8 build, thresholds
    outside [0, 1), the dense (non-paged) oracle, and attention-free
    families are all ValueErrors at construction, never silent fallbacks;
  * pricing — a quantized/sparse engine's default cost model prices
    decode with fewer bytes (same FLOPs) than the fp32 engine's;
  * handoff — a quantized donor ships packed pages + scales that land
    bit-identical on a quantized receiver, and a donor/receiver kv_dtype
    mismatch is an error, never a silent requantization.
"""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import hw
from repro.serving import PartitionEngine, RequestQueue, SimulatedEngine

LENS = [8, 12, 10]
BS = 8

# int8 KV perturbs every cache row by up to scale/2; through attention +
# the LM head the decode logits land well inside 5e-2 on the smoke model
# (measured max |err| ~3.3e-2).  Greedy argmax margins dominate that gap
# on this stream, so tokens are pinned equal as well.
QTOL = dict(rtol=5e-2, atol=5e-2)


@pytest.fixture(scope="module")
def built():
    import jax
    from repro.models import api as mapi

    # float32 so the comparison budget is quantization, not bf16 rounding
    cfg = get_config("qwen2-7b", smoke=True).replace(dtype="float32")
    m = mapi.build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    return cfg, m, params


def _load(queue, lens, gen=4, vocab=256):
    rng = np.random.default_rng(7)
    for p in [rng.integers(1, vocab, size=(l,)).astype(np.int32)
              for l in lens]:
        queue.submit(p, gen)


def _engine(cfg, m, params, **kw):
    kw.setdefault("paged", True)
    return PartitionEngine(cfg, m, params, slots=2, max_len=48,
                           peak_flops=hw.TPU_PEAK_FLOPS, block_size=BS,
                           **kw)


def _drive_pair(cfg, m, params, kw_a, kw_b, tol):
    """Lockstep drive of two engines on identical streams; compares live
    slots' logits under ``tol`` each step and the final greedy tokens."""
    qa, qb = RequestQueue(), RequestQueue()
    _load(qa, LENS, vocab=cfg.vocab)
    _load(qb, LENS, vocab=cfg.vocab)
    ea = _engine(cfg, m, params, **kw_a)
    eb = _engine(cfg, m, params, **kw_b)
    ea.assign(qa.pop(len(LENS)))
    eb.assign(qb.pop(len(LENS)))
    ea.prefill_wave(0.0)
    eb.prefill_wave(0.0)
    steps = 0
    while eb.busy:
        assert ea.busy
        mask = [r is not None for r in eb.active]
        ea.decode_step(0.0)
        eb.decode_step(0.0)
        for i, was_active in enumerate(mask):
            if was_active:
                np.testing.assert_allclose(ea.last_logits[i],
                                           eb.last_logits[i], **tol)
        steps += 1
    assert not ea.busy and steps > 0
    for ra, rb in zip(sorted(ea.completed, key=lambda r: r.rid),
                      sorted(eb.completed, key=lambda r: r.rid)):
        assert ra.rid == rb.rid and ra.tokens == rb.tokens
    return ea, eb


def test_int8_engine_tracks_fp32_oracle(built):
    cfg, m, params = built
    ei, ef = _drive_pair(cfg, m, params, dict(kv_dtype="int8"), {}, QTOL)
    assert ei.pages["k_pages"].dtype == np.int8
    assert "k_scales" in ei.pages and "k_scales" not in ef.pages


def test_sparse_small_threshold_matches_dense(built):
    """At a threshold below any block's attainable attention mass nothing
    is ever dropped, so the sparse decode path must reproduce the dense
    paged engine within base fp tolerance."""
    cfg, m, params = built
    es, _ = _drive_pair(cfg, m, params, dict(sparse_threshold=0.01), {},
                        dict(rtol=2e-4, atol=2e-4))
    assert es.sparse_threshold == 0.01


def test_int8_plus_sparse_compose(built):
    """The two bandwidth levers stack on one engine: packed pages AND
    block skipping, still within the quantization budget of fp32 dense."""
    cfg, m, params = built
    eq, _ = _drive_pair(cfg, m, params,
                        dict(kv_dtype="int8", sparse_threshold=0.01), {},
                        QTOL)
    assert eq.kv_dtype == "int8" and eq.sparse_threshold == 0.01


# ---------------------------------------------------------------------------
# loud refusals: bad layouts fail at construction, never degrade silently
# ---------------------------------------------------------------------------


def _sim(cfg, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 48)
    return SimulatedEngine(cfg, peak_flops=hw.TPU_PEAK_FLOPS,
                           block_size=BS, **kw)


def test_unknown_kv_dtype_rejected():
    cfg = get_config("qwen2-7b", smoke=True)
    with pytest.raises(ValueError, match="unknown kv_dtype"):
        _sim(cfg, kv_dtype="int4")


def test_fp8_is_accepted():
    cfg = get_config("qwen2-7b", smoke=True)
    assert _sim(cfg, kv_dtype="fp8").kv_dtype == "fp8"


def test_sparse_threshold_domain_rejected():
    cfg = get_config("qwen2-7b", smoke=True)
    for bad in (1.0, 1.5, -0.1):
        with pytest.raises(ValueError, match="sparse_threshold"):
            _sim(cfg, sparse_threshold=bad)


def test_attention_free_family_rejected():
    cfg = get_config("mamba2-130m", smoke=True)
    with pytest.raises(ValueError, match="not supported for the 'ssm'"):
        _sim(cfg, kv_dtype="int8")
    with pytest.raises(ValueError, match="not supported for the 'ssm'"):
        _sim(cfg, sparse_threshold=0.1)


def test_dense_oracle_refuses_quant_and_sparse(built):
    """The dense per-wave slab is the bitwise-equivalence oracle: it must
    refuse the layouts it cannot represent rather than approximate them."""
    cfg, m, params = built
    with pytest.raises(ValueError, match="paged block pool"):
        _engine(cfg, m, params, paged=False, kv_dtype="int8")
    with pytest.raises(ValueError, match="paged block pool"):
        _engine(cfg, m, params, paged=False, sparse_threshold=0.1)


# ---------------------------------------------------------------------------
# pricing: the default cost model sees the reduced KV traffic
# ---------------------------------------------------------------------------


def test_default_cost_model_reprices_kv_traffic():
    cfg = get_config("qwen2-7b", smoke=True)
    base = _sim(cfg).cost_model.decode([40, 40])
    i8 = _sim(cfg, kv_dtype="int8").cost_model.decode([40, 40])
    sp = _sim(cfg, sparse_threshold=0.25).cost_model.decode([40, 40])
    assert i8.flops == base.flops and sp.flops == base.flops
    assert i8.byts < base.byts
    assert sp.byts < base.byts


# ---------------------------------------------------------------------------
# handoff: packed pages + scales travel together, layouts never mix
# ---------------------------------------------------------------------------


def test_quantized_handoff_lands_bit_identical(built):
    cfg, m, params = built
    q = RequestQueue()
    _load(q, [10], gen=6, vocab=cfg.vocab)
    src = _engine(cfg, m, params, kv_dtype="int8")
    src.assign(q.pop(1))
    src.prefill_wave(0.0)
    src.decode_step(0.0)
    req, state = src.export_kv(req_rid(src))
    assert state["kv_dtype"] == "int8"
    assert state["pages"]["k"].dtype == np.int8
    assert "k_scales" in state["pages"]

    dst = _engine(cfg, m, params, pid=1, kv_dtype="int8")
    slot = dst.import_kv(req, state)
    tbl = np.asarray(dst.slot_tables[slot], np.int32)
    np.testing.assert_array_equal(
        np.asarray(dst.pages["k_pages"][:, tbl]), state["pages"]["k"])
    np.testing.assert_array_equal(
        np.asarray(dst.pages["k_scales"][:, tbl]),
        state["pages"]["k_scales"])
    while dst.busy:
        dst.decode_step(0.0)
    assert len(dst.completed) == 1
    assert len(dst.completed[0].tokens) == req.max_new_tokens


def req_rid(eng):
    return next(r.rid for r in eng.active if r is not None)


def test_handoff_layout_mismatch_rejected(built):
    cfg, m, params = built
    q = RequestQueue()
    _load(q, [10], gen=6, vocab=cfg.vocab)
    src = _engine(cfg, m, params, kv_dtype="int8")
    src.assign(q.pop(1))
    src.prefill_wave(0.0)
    src.decode_step(0.0)
    req, state = src.export_kv(req_rid(src))
    dst = _engine(cfg, m, params, pid=1)          # fp32 pool
    with pytest.raises(ValueError, match="layout mismatch"):
        dst.import_kv(req, state)
