"""Substrate tests: optimizer, checkpoint round-trip/resume, compression,
partition runtime (sync semantics, failure injection), schedule optimizer,
data pipeline determinism."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.ckpt import CheckpointManager
from repro.configs import SMOKE_SHAPES, get_config
from repro.core.partitioning import (PartitionConfig, sync_bytes_per_step,
                                     weight_replica_bytes)
from repro.core.schedule import aggregate_profile_std, optimize_offsets
from repro.data.pipeline import synth_lm_batch
from repro.models import api as mapi
from repro.models.cnn import model_traces
from repro.optim import (adamw_init, adamw_update, compress_grads,
                         cosine_lr, decompress_grads, init_error_feedback)
from repro.runtime import steps as RS
from repro.runtime.partition_runtime import PartitionRuntime


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_reduces_quadratic():
    params = {"w": jnp.asarray([3.0, -2.0, 1.5])}

    def loss(p):
        return (p["w"] ** 2).sum()

    st_ = adamw_init(params)
    for _ in range(200):
        g = jax.grad(loss)(params)
        params, st_, _ = adamw_update(g, st_, params, lr=0.05,
                                      weight_decay=0.0)
    assert loss(params) < 1e-2


def test_cosine_lr_schedule():
    import numpy as np
    peak = 1e-3
    lrs = [float(cosine_lr(jnp.asarray(s), peak=peak, warmup=10, total=100))
           for s in range(100)]
    assert lrs[0] == 0.0
    assert abs(lrs[10] - peak) < 1e-9
    assert lrs[-1] < peak * 0.2
    assert np.argmax(lrs) == 10


# ---------------------------------------------------------------------------
# gradient compression (error feedback)
# ---------------------------------------------------------------------------


@given(st.integers(0, 1000))
@settings(max_examples=20, deadline=None)
def test_compression_error_feedback_converges(seed):
    """With EF, the accumulated compressed sum tracks the true sum."""
    rng = np.random.default_rng(seed)
    g = {"w": jnp.asarray(rng.standard_normal(64), jnp.float32)}
    err = init_error_feedback(g)
    total_q = np.zeros(64)
    for _ in range(16):
        q, err = compress_grads(g, err)
        total_q += np.asarray(decompress_grads(q)["w"])
    true = np.asarray(g["w"]) * 16
    np.testing.assert_allclose(total_q, true, atol=np.abs(true).max() * 0.02
                               + 1e-3)


def test_compression_ratio():
    g = {"w": jnp.zeros((1024,), jnp.float32)}
    q, _ = compress_grads(g, init_error_feedback(g))
    qbytes = q["w"][0].nbytes + 4
    assert qbytes <= g["w"].nbytes / 4 + 16  # int8 = 4x smaller than f32


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip_and_keep_k(tmp_path):
    cm = CheckpointManager(tmp_path, keep=2)
    state = {"params": {"w": jnp.arange(6.0).reshape(2, 3)},
             "opt": {"m": jnp.zeros((2, 3))}}
    for s in (1, 2, 3):
        cm.save(s, state, meta={"tag": s})
    assert cm.steps() == [2, 3]
    restored, meta = cm.restore(state)
    np.testing.assert_array_equal(restored["params"]["w"],
                                  state["params"]["w"])
    assert meta["step"] == 3


def test_checkpoint_resume_exact(tmp_path):
    """Train 6 steps straight == train 3, checkpoint, restore, train 3."""
    cfg = get_config("mamba2_130m", smoke=True)
    api = mapi.build(cfg)
    shape = SMOKE_SHAPES["train_4k"]
    step_fn = jax.jit(RS.make_train_step(api))

    def run(params, opt, start, n):
        for s in range(start, start + n):
            params, opt, m = step_fn(params, opt, _b(s))
        return params, opt, m

    def _b(s):
        return {k: jnp.asarray(v) for k, v in
                synth_lm_batch(cfg, shape, s).items()}

    p0 = api.init(jax.random.PRNGKey(0))
    o0 = adamw_init(p0)
    pa, oa, ma = run(p0, o0, 0, 6)

    p1, o1, _ = run(api.init(jax.random.PRNGKey(0)), adamw_init(p0), 0, 3)
    cm = CheckpointManager(tmp_path)
    cm.save(3, {"params": p1, "opt": o1._asdict()})
    st, meta = cm.restore({"params": p1, "opt": o1._asdict()})
    o1r = o1._replace(**{k: st["opt"][k] for k in ("step", "m", "v")})
    pb, ob, mb = run(st["params"], o1r, 3, 3)

    np.testing.assert_allclose(float(ma["loss"]), float(mb["loss"]),
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# partition runtime: sync + failure + straggler semantics
# ---------------------------------------------------------------------------


def _mk_runtime(partitions=2, sync_every=2):
    from repro.configs.base import ShapeCell
    cfg = get_config("qwen2_7b", smoke=True)
    api = mapi.build(cfg)
    pc = PartitionConfig(partitions=partitions, sync_every=sync_every)
    step = RS.make_train_step(api, peak_lr=5e-3, warmup=2, total=60)
    rt = PartitionRuntime(api, step, pc, jax.random.PRNGKey(0))
    shape = ShapeCell("train", 64, 2 * partitions, "train")

    def make_batches(step):
        b = synth_lm_batch(cfg, shape, step, partitions=partitions)
        return [{k: jnp.asarray(v[i]) for k, v in b.items()}
                for i in range(partitions)]

    return rt, make_batches


def test_partitions_diverge_then_sync():
    rt, mb = _mk_runtime(2, sync_every=4)
    for s in range(3):
        rt.run_round(mb(s))
        rt.maybe_sync()
    # before sync point: replicas differ
    w0 = jax.tree.leaves(rt.parts[0].params)[0]
    w1 = jax.tree.leaves(rt.parts[1].params)[0]
    assert not np.allclose(np.asarray(w0, np.float32),
                           np.asarray(w1, np.float32))
    rt.run_round(mb(3))
    assert rt.maybe_sync()  # 4th step triggers sync
    w0 = jax.tree.leaves(rt.parts[0].params)[0]
    w1 = jax.tree.leaves(rt.parts[1].params)[0]
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))


def test_partition_failure_and_replacement():
    rt, mb = _mk_runtime(3, sync_every=2)
    losses = rt.train(lambda s: mb(s), 4, fail_at={1: 2})
    assert len(rt.alive_parts()) == 2
    assert all(np.isfinite(list(l.values())).all() for l in losses)
    rt.add_partition(2)
    assert len(rt.alive_parts()) == 3
    rt.run_round(mb(9))
    rt.sync()


def test_training_reduces_loss_partitioned():
    rt, mb = _mk_runtime(2, sync_every=2)
    losses = rt.train(lambda s: mb(s % 4), 14)
    first = np.mean(list(losses[0].values()))
    last = np.mean([np.mean(list(l.values())) for l in losses[-3:]])
    assert last < first  # synthetic Zipf data is learnable


# ---------------------------------------------------------------------------
# partitioning math + schedule optimizer
# ---------------------------------------------------------------------------


@given(st.integers(1, 16), st.integers(1, 64))
@settings(max_examples=50, deadline=None)
def test_partitioning_accounting(p, w):
    n = 1_000_000
    rep = weight_replica_bytes(n, p)
    assert rep == (p - 1) * 2 * n
    sync = sync_bytes_per_step(n, p, w)
    if p == 1:
        assert sync == 0
    else:
        np.testing.assert_allclose(sync * w, 2 * n * 2, rtol=1e-12)


def test_offset_optimizer_beats_aligned():
    tr = model_traces("resnet50")
    for P in (4, 8):
        opt = optimize_offsets(tr, P, 64 // P, 64 // P)
        s_opt, _ = aggregate_profile_std(tr, opt, 64 // P, 64 // P)
        s_non, _ = aggregate_profile_std(tr, np.zeros(P), 64 // P, 64 // P)
        uni = np.arange(P) / P
        s_uni, _ = aggregate_profile_std(tr, uni, 64 // P, 64 // P)
        assert s_opt < s_non
        assert s_opt <= s_uni * 1.001


# ---------------------------------------------------------------------------
# data pipeline determinism
# ---------------------------------------------------------------------------


def test_pipeline_deterministic_and_step_dependent():
    cfg = get_config("qwen2_7b", smoke=True)
    shape = SMOKE_SHAPES["train_4k"]
    a = synth_lm_batch(cfg, shape, 7)
    b = synth_lm_batch(cfg, shape, 7)
    c = synth_lm_batch(cfg, shape, 8)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert a["tokens"].max() < cfg.vocab
