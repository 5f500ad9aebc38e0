"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.conv2d import ops as conv_ops
from repro.kernels.conv2d import ref as conv_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.matmul import ops as mm_ops
from repro.kernels.matmul import ref as mm_ref

TOL = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,K,N,bm,bk,bn", [
    (128, 256, 128, 64, 128, 64),
    (256, 512, 384, 128, 256, 128),
    (64, 64, 64, 64, 64, 64),
    (512, 128, 256, 256, 128, 128),
])
def test_matmul(M, K, N, bm, bk, bn, dtype):
    a = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32).astype(dtype)
    b = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32).astype(dtype)
    out = mm_ops.matmul(a, b, bm=bm, bk=bk, bn=bn, interpret=True)
    ref = mm_ref.matmul(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Hq,Hkv,D,causal,window", [
    (2, 128, 4, 4, 32, True, 0),     # MHA causal
    (2, 256, 8, 2, 64, True, 0),     # GQA
    (1, 256, 8, 2, 64, True, 64),    # sliding window
    (2, 128, 4, 1, 32, False, 0),    # MQA bidirectional
])
def test_flash_attention(B, S, Hq, Hkv, D, causal, window, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32).astype(dtype)
    out = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                 bq=64, bk=64, interpret=True)
    ref = fa_ref.attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("N,H,W,C,K,kh,stride", [
    (2, 16, 16, 32, 64, 3, 1),
    (2, 16, 16, 32, 64, 3, 2),
    (1, 14, 14, 16, 32, 1, 1),   # pointwise
    (1, 12, 12, 8, 16, 5, 2),
])
def test_conv2d(N, H, W, C, K, kh, stride, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], (N, H, W, C), jnp.float32).astype(dtype)
    w = (jax.random.normal(ks[1], (kh, kh, C, K), jnp.float32) * 0.1).astype(dtype)
    out = conv_ops.conv2d(x, w, stride=stride, padding="SAME", tk=K,
                          interpret=True)
    ref = conv_ref.conv2d(x, w, stride=stride)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Hq,Hkv,D,cur,window,bk", [
    (2, 256, 8, 2, 64, 100, 0, 64),    # GQA, partial cache
    (1, 512, 4, 4, 32, 511, 0, 128),   # MHA, full cache
    (2, 256, 8, 2, 64, 200, 64, 64),   # sliding window
    (1, 128, 8, 1, 64, 0, 0, 64),      # MQA, first token
])
def test_flash_decode(B, S, Hq, Hkv, D, cur, window, bk, dtype):
    from repro.kernels.flash_decode import ops as fd_ops
    from repro.kernels.flash_decode import ref as fd_ref
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32).astype(dtype)
    kc = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32).astype(dtype)
    vc = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32).astype(dtype)
    pos = jnp.asarray(cur, jnp.int32)
    out = fd_ops.flash_decode(q, kc, vc, pos, window=window, bk=bk,
                              interpret=True)
    ref = fd_ref.decode_attention(q, kc, vc, pos, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_flash_decode_matches_model_decode():
    """Kernel agrees with the in-model decode attention (layers.py)."""
    from repro.kernels.flash_decode import ops as fd_ops
    from repro.models.layers import decode_attention as model_decode
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, S, Hq, Hkv, D = 2, 128, 4, 2, 32
    q = jax.random.normal(ks[0], (B, 1, Hq, D))
    kc = jax.random.normal(ks[1], (B, S, Hkv, D))
    vc = jax.random.normal(ks[2], (B, S, Hkv, D))
    pos = jnp.asarray(77, jnp.int32)
    out_k = fd_ops.flash_decode(q[:, 0], kc, vc, pos, bk=64, interpret=True)
    out_m = model_decode(q, kc, vc, pos)[:, 0]
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_m),
                               rtol=2e-4, atol=2e-4)


def _paged_pool(rng, B, T, N, bs, Hkv, D, dtype, lens):
    """Random pool + per-slot block tables covering ``lens`` tokens each."""
    kp = jax.random.normal(jax.random.PRNGKey(3), (N, bs, Hkv, D),
                          jnp.float32).astype(dtype)
    vp = jax.random.normal(jax.random.PRNGKey(4), (N, bs, Hkv, D),
                          jnp.float32).astype(dtype)
    free = list(rng.permutation(np.arange(1, N)))
    tables = np.zeros((B, T), np.int32)
    for b, l in enumerate(lens):
        for t in range((l + bs - 1) // bs):
            tables[b, t] = free.pop()
    return kp, vp, jnp.asarray(tables)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Hq,Hkv,D,bs,window", [
    (3, 4, 2, 32, 16, 0),     # GQA, ragged lengths
    (2, 8, 8, 64, 32, 0),     # MHA
    (2, 8, 2, 64, 16, 48),    # sliding window
    (1, 8, 1, 32, 16, 0),     # MQA
])
def test_paged_decode_attention(B, Hq, Hkv, D, bs, window, dtype):
    from repro.kernels.paged_attention import ops as pa_ops
    from repro.kernels.paged_attention import ref as pa_ref
    rng = np.random.default_rng(0)
    T, N = 4, 1 + 4 * B
    lens = [int(x) for x in rng.integers(1, T * bs, size=B)]
    kp, vp, tables = _paged_pool(rng, B, T, N, bs, Hkv, D, dtype, lens)
    q = jax.random.normal(jax.random.PRNGKey(5), (B, Hq, D),
                          jnp.float32).astype(dtype)
    cur = jnp.asarray([l - 1 for l in lens], jnp.int32)
    out = pa_ops.paged_decode(q, kp, vp, tables, cur, window=window,
                              interpret=True)
    ref = pa_ref.paged_decode_attention(q, kp, vp, tables, cur,
                                        window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_paged_ref_matches_dense_decode():
    """The paged oracle equals dense decode attention on the gathered
    contiguous cache (same per-slot masking semantics)."""
    from repro.kernels.paged_attention import ref as pa_ref
    from repro.models.layers import decode_attention as model_decode
    rng = np.random.default_rng(1)
    B, Hq, Hkv, D, bs, T, N = 2, 4, 2, 32, 8, 4, 12
    lens = [13, 27]
    kp, vp, tables = _paged_pool(rng, B, T, N, bs, Hkv, D, jnp.float32, lens)
    q = jax.random.normal(jax.random.PRNGKey(6), (B, 1, Hq, D))
    cur = jnp.asarray([l - 1 for l in lens], jnp.int32)
    kd = kp[tables].reshape(B, T * bs, Hkv, D)
    vd = vp[tables].reshape(B, T * bs, Hkv, D)
    out_p = pa_ref.paged_decode_attention(q[:, 0], kp, vp, tables, cur)
    out_d = model_decode(q, kd, vd, cur)[:, 0]
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_d),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# quantized + blockwise-sparse paged decode
# ---------------------------------------------------------------------------

# int8 KV rounds to nearest inside a per-(block, kv-head) abs-max scale, so
# the per-element cache error is bounded by scale/2; through the softmax the
# attention output lands well inside 5e-2 on these shapes (measured ~1e-2).
# This budget is for quant-vs-DENSE only — kernel-vs-quant-oracle runs at the
# base TOL because both sides do the identical dequant multiply.
QTOL = dict(rtol=5e-2, atol=5e-2)


def _quantized_pool(rng, B, T, N, bs, Hkv, D, lens):
    from repro.serving.kv_pool import quantize_kv
    kp, vp, tables = _paged_pool(rng, B, T, N, bs, Hkv, D, jnp.float32, lens)
    kq, ks = quantize_kv(kp, "int8")
    vq, vs = quantize_kv(vp, "int8")
    return kp, vp, (kq, ks, vq, vs), tables


@pytest.mark.parametrize("B,Hq,Hkv,D,bs,window", [
    (3, 4, 2, 32, 16, 0),     # GQA, ragged lengths
    (2, 8, 8, 64, 32, 0),     # MHA
    (2, 8, 2, 64, 16, 48),    # sliding window
    (1, 8, 1, 32, 16, 0),     # MQA
])
def test_paged_decode_quant(B, Hq, Hkv, D, bs, window):
    """Quantized Pallas kernel vs the quantized oracle at base tolerance,
    and the quantized oracle vs the dense fp32 ref inside QTOL."""
    from repro.kernels.paged_attention import ops as pa_ops
    from repro.kernels.paged_attention import ref as pa_ref
    rng = np.random.default_rng(0)
    T, N = 4, 1 + 4 * B
    lens = [int(x) for x in rng.integers(1, T * bs, size=B)]
    kp, vp, (kq, ks, vq, vs), tables = \
        _quantized_pool(rng, B, T, N, bs, Hkv, D, lens)
    q = jax.random.normal(jax.random.PRNGKey(5), (B, Hq, D))
    cur = jnp.asarray([l - 1 for l in lens], jnp.int32)
    out = pa_ops.paged_decode_quant(q, kq, vq, ks, vs, tables, cur,
                                    window=window, interpret=True)
    ref = pa_ref.paged_decode_attention_quant(q, kq, vq, ks, vs, tables,
                                              cur, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **TOL[jnp.float32])
    dense = pa_ref.paged_decode_attention(q, kp, vp, tables, cur,
                                          window=window)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(dense), **QTOL)


def test_quant_oracle_matches_explicit_dequant():
    """The quantized oracle equals the dense oracle run on explicitly
    dequantized pages — dequant-in-kernel changes arithmetic order only."""
    from repro.kernels.paged_attention import ref as pa_ref
    from repro.serving.kv_pool import dequantize_kv
    rng = np.random.default_rng(2)
    B, Hq, Hkv, D, bs, T, N = 2, 4, 2, 32, 8, 4, 12
    lens = [13, 27]
    _, _, (kq, ks, vq, vs), tables = \
        _quantized_pool(rng, B, T, N, bs, Hkv, D, lens)
    q = jax.random.normal(jax.random.PRNGKey(7), (B, Hq, D))
    cur = jnp.asarray([l - 1 for l in lens], jnp.int32)
    out_q = pa_ref.paged_decode_attention_quant(q, kq, vq, ks, vs,
                                                tables, cur)
    out_d = pa_ref.paged_decode_attention(q, dequantize_kv(kq, ks),
                                          dequantize_kv(vq, vs), tables, cur)
    np.testing.assert_allclose(np.asarray(out_q), np.asarray(out_d),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,Hq,Hkv,D,bs,threshold,window", [
    (3, 4, 2, 32, 16, 0.05, 0),    # GQA, ragged lengths
    (2, 8, 8, 64, 32, 0.10, 0),    # MHA
    (2, 8, 2, 64, 16, 0.05, 48),   # sliding window
    (1, 8, 1, 32, 16, 0.20, 0),    # MQA, aggressive threshold
])
def test_paged_decode_sparse(B, Hq, Hkv, D, bs, threshold, window):
    """Sparse Pallas kernel vs the sparse oracle at base tolerance — both
    consume the same ``block_keep_mask``, so selection cannot diverge and
    only the attention arithmetic is under test."""
    from repro.kernels.paged_attention import ops as pa_ops
    from repro.kernels.paged_attention import ref as pa_ref
    rng = np.random.default_rng(0)
    T, N = 4, 1 + 4 * B
    lens = [int(x) for x in rng.integers(1, T * bs, size=B)]
    kp, vp, tables = _paged_pool(rng, B, T, N, bs, Hkv, D, jnp.float32, lens)
    q = jax.random.normal(jax.random.PRNGKey(5), (B, Hq, D))
    cur = jnp.asarray([l - 1 for l in lens], jnp.int32)
    out = pa_ops.paged_decode_sparse(q, kp, vp, tables, cur,
                                     threshold=threshold, window=window,
                                     interpret=True)
    ref = pa_ref.paged_decode_attention_sparse(q, kp, vp, tables, cur,
                                               threshold=threshold,
                                               window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **TOL[jnp.float32])


def test_sparse_threshold_zero_is_dense():
    """threshold=0 keeps every valid block: the sparse oracle coincides
    with the dense oracle exactly, and the sparse kernel matches the dense
    kernel within base tolerance."""
    from repro.kernels.paged_attention import ops as pa_ops
    from repro.kernels.paged_attention import ref as pa_ref
    rng = np.random.default_rng(3)
    B, Hq, Hkv, D, bs, T, N = 2, 8, 2, 64, 16, 4, 12
    lens = [21, 55]
    kp, vp, tables = _paged_pool(rng, B, T, N, bs, Hkv, D, jnp.float32, lens)
    q = jax.random.normal(jax.random.PRNGKey(8), (B, Hq, D))
    cur = jnp.asarray([l - 1 for l in lens], jnp.int32)
    ref_s = pa_ref.paged_decode_attention_sparse(q, kp, vp, tables, cur,
                                                 threshold=0.0)
    ref_d = pa_ref.paged_decode_attention(q, kp, vp, tables, cur)
    np.testing.assert_array_equal(np.asarray(ref_s), np.asarray(ref_d))
    out_s = pa_ops.paged_decode_sparse(q, kp, vp, tables, cur, threshold=0.0,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(ref_d),
                               rtol=2e-4, atol=2e-4)


def test_block_keep_mask_invariants():
    """Selection invariants: the block holding cur_pos is always kept,
    nothing past cur_pos is ever kept, threshold=0 keeps exactly the valid
    blocks, and packed pages + scales select identically to the
    dequantized pages (the per-block scale commutes with the mean)."""
    from repro.kernels.paged_attention.ref import block_keep_mask
    from repro.serving.kv_pool import dequantize_kv, quantize_kv
    rng = np.random.default_rng(4)
    B, Hq, Hkv, D, bs, T, N = 3, 4, 2, 32, 8, 5, 16
    lens = [5, 17, 39]
    kp, _, tables = _paged_pool(rng, B, T, N, bs, Hkv, D, jnp.float32, lens)
    q = jax.random.normal(jax.random.PRNGKey(9), (B, Hq, D))
    cur = jnp.asarray([l - 1 for l in lens], jnp.int32)
    for thr in (0.0, 0.1, 0.5):
        keep = np.asarray(block_keep_mask(q, kp, tables, cur, threshold=thr))
        for b, l in enumerate(lens):
            nblk = (l + bs - 1) // bs
            assert keep[b, :, (l - 1) // bs].all()       # cur block kept
            assert not keep[b, :, nblk:].any()           # nothing past cur
            if thr == 0.0:
                assert keep[b, :, :nblk].all()           # dense at zero
    kq, ks = quantize_kv(kp, "int8")
    keep_q = block_keep_mask(q, kq, tables, cur, threshold=0.1, k_scales=ks)
    keep_f = block_keep_mask(q, dequantize_kv(kq, ks), tables, cur,
                             threshold=0.1)
    np.testing.assert_array_equal(np.asarray(keep_q), np.asarray(keep_f))


def test_xla_flash_matches_naive():
    """The in-model chunked-scan attention equals the materialized oracle."""
    from repro.models.layers import flash_attention, naive_attention
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (2, 96, 4, 32))
    k = jax.random.normal(ks[1], (2, 96, 2, 32))
    v = jax.random.normal(ks[2], (2, 96, 2, 32))
    for w in (None, 24):
        out = flash_attention(q, k, v, causal=True, window=w,
                              q_chunk=32, kv_chunk=48)
        ref = naive_attention(q, k, v, causal=True, window=w)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
