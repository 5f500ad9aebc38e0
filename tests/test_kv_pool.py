"""BlockPool allocator invariants: alloc/free round-trips never double-
assign a block, exhaustion is a hard report (never a silent truncation),
freed blocks are immediately reusable, and — with the prefix index on —
reference-shared chains free/evict without ever double-freeing or
reclaiming a live block.  Property tests run through the
optional-hypothesis shim; the plain tests pin the same invariants without
it."""
from collections import Counter

import pytest

from hypothesis import given, settings, strategies as st
from repro.serving.kv_pool import NULL_BLOCK, BlockPool, PoolExhausted


# ---------------------------------------------------------------------------
# plain unit tests (always run)
# ---------------------------------------------------------------------------


def test_null_block_reserved_and_capacity():
    pool = BlockPool(9, 4)
    assert pool.n_free == 8          # block 0 is the null block
    assert pool.blocks_for(1) == 1
    assert pool.blocks_for(4) == 1
    assert pool.blocks_for(5) == 2
    assert pool.can_fit(32) and not pool.can_fit(33)


def test_alloc_unique_and_never_null():
    pool = BlockPool(17, 8)
    got = pool.alloc(16)
    assert len(got) == 16 == len(set(got))
    assert NULL_BLOCK not in got


def test_exhaustion_raises_and_leaves_pool_intact():
    pool = BlockPool(5, 8)
    live = pool.alloc(3)
    with pytest.raises(PoolExhausted):
        pool.alloc(2)                # only 1 free: all-or-nothing
    assert pool.n_free == 1          # the failed alloc took nothing
    pool.free(live)
    assert pool.n_free == 4


def test_freed_blocks_are_reusable():
    pool = BlockPool(5, 8)
    a = pool.alloc(4)
    pool.free(a)
    b = pool.alloc(4)
    assert sorted(a) == sorted(b)


def test_double_free_and_foreign_free_rejected():
    pool = BlockPool(6, 8)
    a = pool.alloc(2)
    pool.free(a)
    with pytest.raises(ValueError):
        pool.free(a)                 # already free
    with pytest.raises(ValueError):
        pool.free([5])               # never allocated
    pool.free([NULL_BLOCK])          # the null block is always a no-op


def test_free_mixed_live_dead_is_all_or_nothing():
    """Regression: a free list mixing live and dead ids must raise WITHOUT
    freeing the live ones — the old code freed prefix-of-list before hitting
    the bad id, leaving the pool half-mutated."""
    pool = BlockPool(8, 4)
    a = pool.alloc(2)
    b = pool.alloc(2)
    pool.free(a)
    before = pool.n_free
    with pytest.raises(ValueError):
        pool.free([b[0], a[0], b[1]])   # a[0] is dead: whole call rejected
    assert pool.n_free == before        # b's blocks are still live...
    pool.free(b)                        # ...and freeable in one piece
    assert pool.n_free == 7
    c = pool.alloc(1)
    with pytest.raises(ValueError):
        # one live id listed more times than it holds references
        pool.free([c[0], c[0]])
    assert pool.refcount(c[0]) == 1     # over-free mutated nothing


def test_zero_token_budget_needs_no_blocks():
    pool = BlockPool(5, 8)
    assert pool.blocks_for(0) == 0      # was 1: an empty chain burnt a block
    assert pool.blocks_for(-3) == 0
    assert pool.alloc_for_tokens(0) == []
    assert pool.n_free == 4 and pool.can_fit(0)


def test_write_prefix_pages_rejects_overflow():
    """A prefix longer than the table capacity raises instead of silently
    truncating context (the pad<0 path used to wrap around)."""
    jnp = pytest.importorskip("jax.numpy")
    from repro.serving.kv_pool import write_prefix_pages

    L, B, Hkv, D, bs, T = 1, 1, 1, 2, 4, 2
    pages = {"k_pages": jnp.zeros((L, 8, bs, Hkv, D)),
             "v_pages": jnp.zeros((L, 8, bs, Hkv, D))}
    tables = jnp.asarray([[1, 2]], jnp.int32)
    good = jnp.ones((L, B, T * bs, Hkv, D))
    write_prefix_pages(pages, good, good, tables)   # exactly full: fine
    bad = jnp.ones((L, B, T * bs + 1, Hkv, D))
    with pytest.raises(ValueError, match="never silently truncates"):
        write_prefix_pages(pages, bad, bad, tables)


# ---------------------------------------------------------------------------
# quantized layout: round-trip error bound + quantize-on-append scatter
# ---------------------------------------------------------------------------


def test_quantize_round_trip_int8_bound():
    """int8 rounds to nearest within a per-(block, kv-head) abs-max scale,
    so every element round-trips within scale/2 (plus f32 slack)."""
    jnp = pytest.importorskip("jax.numpy")
    import numpy as np
    from repro.serving.kv_pool import dequantize_kv, quantize_kv

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((3, 8, 2, 16)) * 5.0, jnp.float32)
    q, s = quantize_kv(x, "int8")
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert s.shape == (3, 2) and s.dtype == jnp.float32
    err = np.abs(np.asarray(dequantize_kv(q, s)) - np.asarray(x))
    bound = np.asarray(s)[:, None, :, None] * (0.5 + 1e-5)
    assert (err <= bound).all()
    # all-zero input: the scale floor keeps the round-trip exact
    zq, zs = quantize_kv(jnp.zeros_like(x), "int8")
    assert not np.asarray(dequantize_kv(zq, zs)).any()


@given(st.integers(0, 2**16), st.floats(1e-4, 1e4),
       st.integers(1, 4), st.integers(1, 3), st.integers(1, 8))
@settings(max_examples=40, deadline=None)
def test_quantize_round_trip_property(seed, amp, nblk, hkv, d):
    """Property form of the scale/2 bound over random shapes/amplitudes:
    dequantize(quantize(x)) never strays more than half a quantization
    step from x, element-wise, for any (block, head) tile."""
    jnp = pytest.importorskip("jax.numpy")
    import numpy as np
    from repro.serving.kv_pool import dequantize_kv, quantize_kv

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((nblk, 4, hkv, d)) * amp,
                    jnp.float32)
    q, s = quantize_kv(x, "int8")
    err = np.abs(np.asarray(dequantize_kv(q, s)) - np.asarray(x))
    bound = np.asarray(s)[:, None, :, None] * (0.5 + 1e-5) + 1e-9
    assert (err <= bound).all()


def test_write_prefix_pages_quantized_scatter():
    """The quantize-on-append path: written blocks carry packed values +
    fresh per-(layer, block, head) scales that round-trip the prefix
    within scale/2; blocks outside the tables stay untouched."""
    jnp = pytest.importorskip("jax.numpy")
    import numpy as np
    from repro.serving.kv_pool import dequantize_kv, write_prefix_pages

    L, B, Hkv, D, bs, T, N = 2, 1, 2, 4, 4, 2, 8
    pages = {"k_pages": jnp.zeros((L, N, bs, Hkv, D), jnp.int8),
             "v_pages": jnp.zeros((L, N, bs, Hkv, D), jnp.int8),
             "k_scales": jnp.zeros((L, N, Hkv), jnp.float32),
             "v_scales": jnp.zeros((L, N, Hkv), jnp.float32)}
    tables = jnp.asarray([[2, 5]], jnp.int32)
    rng = np.random.default_rng(1)
    k = jnp.asarray(rng.standard_normal((L, B, T * bs, Hkv, D)) * 3.0,
                    jnp.float32)
    v = jnp.asarray(rng.standard_normal((L, B, T * bs, Hkv, D)) * 3.0,
                    jnp.float32)
    out = write_prefix_pages(pages, k, v, tables)
    assert out["k_pages"].dtype == jnp.int8
    for pk, sk, src in (("k_pages", "k_scales", k), ("v_pages", "v_scales", v)):
        blk = out[pk][:, tables[0]]               # (L, T, bs, Hkv, D)
        scl = out[sk][:, tables[0]]               # (L, T, Hkv)
        got = np.asarray(dequantize_kv(blk, scl))
        want = np.asarray(src).reshape(L, T, bs, Hkv, D)
        bound = np.asarray(scl)[:, :, None, :, None] * (0.5 + 1e-5) + 1e-9
        assert (np.abs(got - want) <= bound).all()
        untouched = np.ones(N, bool)
        untouched[[2, 5]] = False
        assert not np.asarray(out[pk])[:, untouched].any()
        assert not np.asarray(out[sk])[:, untouched].any()


# ---------------------------------------------------------------------------
# prefix index: sharing, copy-on-write, LRU eviction
# ---------------------------------------------------------------------------


def test_alloc_chain_shares_full_blocks_and_cows_partial():
    pool = BlockPool(20, 4, prefix_cache=True)
    key = list(range(100, 110))                   # 10 tokens: 2 full + 2
    ca1 = pool.alloc_chain(key, 12)
    assert ca1.cached_tokens == 0 and ca1.shared_blocks == 0
    pool.register_chain(key, ca1.table, 10)
    ca2 = pool.alloc_chain(key, 12)
    assert ca2.table[:2] == ca1.table[:2]         # full blocks shared
    assert ca2.table[2] != ca1.table[2]           # partial never shared
    assert ca2.shared_blocks == 2
    assert (ca2.cow_src, ca2.cow_len) == (ca1.table[2], 2)
    assert ca2.cached_tokens == 2 * 4 + 2         # full blocks + COW prefix
    assert pool.refcount(ca1.table[0]) == 2
    assert pool.n_hits == 1 and pool.n_cow == 1


def test_last_table_entry_is_always_owned():
    """Decode appends land in the last table entry, so even a whole-prompt
    hit must leave it owned (refcount 1, unshared)."""
    pool = BlockPool(20, 4, prefix_cache=True)
    key = list(range(8))                          # exactly 2 full blocks
    ca1 = pool.alloc_chain(key, 8)
    pool.register_chain(key, ca1.table, 8)
    ca2 = pool.alloc_chain(key, 8)
    assert ca2.table[0] == ca1.table[0]           # head shared
    assert ca2.table[1] != ca1.table[1]           # tail owned
    assert ca2.shared_blocks == 1
    assert pool.refcount(ca2.table[1]) == 1


def test_freed_published_blocks_park_cached_then_resurrect():
    pool = BlockPool(20, 4, prefix_cache=True)
    key = list(range(12))
    ca = pool.alloc_chain(key, 12)
    pool.register_chain(key, ca.table, 12)
    free_before = pool.n_free
    pool.free(ca.table)
    assert pool.n_live == 0
    assert pool.n_cached == 3                     # published: evictable,
    assert pool.n_free == free_before             # NOT back on the free list
    hit = pool.alloc_chain(key, 16)
    assert hit.table[:3] == ca.table[:3]          # resurrected, same ids
    assert hit.cached_tokens == 12
    assert all(pool.refcount(b) == 1 for b in hit.table)


def test_eviction_reclaims_lru_and_spares_live_chains():
    pool = BlockPool(7, 4, prefix_cache=True)     # 6 usable blocks
    cold_key = list(range(200, 208))
    cold = pool.alloc_chain(cold_key, 8)          # 2 blocks, then cached
    pool.register_chain(cold_key, cold.table, 8)
    pool.free(cold.table)
    hot = pool.alloc_chain(list(range(300, 312)), 12)   # 3 live blocks
    assert pool.n_free == 1 and pool.n_cached == 2
    got = pool.alloc(3)                           # needs 2 evictions
    assert pool.n_evicted == 2
    assert not set(got) & set(hot.table)          # live chain untouched
    assert pool.peek_cached_tokens(cold_key) == 0  # index entries dropped
    with pytest.raises(PoolExhausted):
        pool.alloc(1)                             # nothing left to evict
    assert pool.refcount(hot.table[0]) == 1       # failed alloc took nothing


def test_alloc_chain_rolls_back_shared_refs_on_exhaustion():
    pool = BlockPool(4, 4, prefix_cache=True)     # 3 usable blocks
    key = list(range(8))
    ca = pool.alloc_chain(key, 8)
    pool.register_chain(key, ca.table, 8)
    pool.free(ca.table)                           # both blocks parked cached
    with pytest.raises(PoolExhausted):
        pool.alloc_chain(key + list(range(8, 20)), 20)  # needs 5 blocks
    assert pool.n_live == 0                       # shared incref rolled back
    assert pool.n_cached == 2                     # ...and re-parked
    again = pool.alloc_chain(key, 8)              # cache still serves hits
    assert again.cached_tokens == 4


# ---------------------------------------------------------------------------
# property tests (model-based alloc/free interleaving)
# ---------------------------------------------------------------------------


@given(st.integers(2, 48),
       st.lists(st.integers(0, 12), min_size=1, max_size=60))
@settings(max_examples=60, deadline=None)
def test_alloc_free_round_trip_invariants(n_blocks, sizes):
    """Random alloc/free interleavings: no block is ever live twice, the
    free count always balances, and exhaustion is all-or-nothing."""
    pool = BlockPool(n_blocks, 4)
    live = []
    for step, k in enumerate(sizes):
        if step % 3 == 2 and live:           # free the oldest allocation
            pool.free(live.pop(0))
        else:
            before = pool.n_free
            try:
                got = pool.alloc(k)
            except PoolExhausted:
                assert k > before            # only a true shortfall raises
                assert pool.n_free == before  # ...and takes nothing
                continue
            assert len(got) == k and NULL_BLOCK not in got
            assert not set(got) & {b for g in live for b in g}
            live.append(got)
        flat = [b for g in live for b in g]
        assert len(flat) == len(set(flat))   # never double-assigned
        assert pool.n_free + len(flat) == n_blocks - 1
    for g in live:
        pool.free(g)
    assert pool.n_free == n_blocks - 1 and pool.n_live == 0


@given(st.lists(st.tuples(st.integers(0, 2), st.integers(1, 4),
                          st.booleans()),
                min_size=1, max_size=40))
@settings(max_examples=50, deadline=None)
def test_prefix_cache_refcount_and_eviction_invariants(ops):
    """Random alloc_chain/register/free interleavings over three hot keys:
    every block's refcount equals the number of live chains holding it, no
    live block ever reappears on the free list (eviction spares live
    chains), the free/live/cached partition always conserves the pool, and
    the final teardown frees every chain exactly once (no double free of
    still-referenced shared blocks)."""
    pool = BlockPool(12, 4, prefix_cache=True)
    keys = [[k * 100 + t for t in range(10)] for k in range(3)]
    live = []
    for key_i, nblk, do_free in ops:
        if do_free and live:
            pool.free(live.pop(0))
        else:
            try:
                ca = pool.alloc_chain(keys[key_i], nblk * 4)
            except PoolExhausted:
                pass
            else:
                pool.register_chain(keys[key_i], ca.table, nblk * 4)
                live.append(ca.table)
        held = Counter(b for t in live for b in t)
        assert all(pool.refcount(b) == c for b, c in held.items())
        assert pool.n_live == len(held)
        assert not set(pool._free) & set(held)
        assert pool.n_free + pool.n_live + pool.n_cached \
            == pool.n_blocks - 1
    for t in live:
        pool.free(t)                     # shared refs unwind one at a time
    assert pool.n_live == 0
    assert pool.n_free + pool.n_cached == pool.n_blocks - 1


@given(st.lists(st.integers(1, 6), min_size=1, max_size=20))
@settings(max_examples=40, deadline=None)
def test_free_then_realloc_conserves_identity(sizes):
    """Every freed block returns to circulation: allocating after freeing
    everything always yields the same id universe."""
    pool = BlockPool(32, 4)
    universe = set(pool.alloc(31))
    pool.free(sorted(universe))
    for k in sizes:
        got = pool.alloc(k)
        assert set(got) <= universe
        pool.free(got)
