"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler compiles here for the ``v5e:2x2`` topology it is told
about, so these tests refuse what the chip's compiler would refuse — a
block not aligned to the tiling, a kernel over its VMEM, a program over
the chip's memory — at no chip time.  Nothing runs: the tests pass shapes
(``jax.eval_shape``), never arrays.  Every kept Pallas kernel compiles at
the widths of Qwen1.5-4B (20 of 20 heads) and Qwen2-7B (28 query over 4 KV
heads), or its ``ops.py`` refuses the shape before Mosaic sees it.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and each test worker imports every test
file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.conv2d import ops as conv_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_decode import ops as fd_ops
from repro.kernels.matmul import ops as mm_ops
from repro.kernels.paged_attention import ops as pa_ops
from repro.models import api as mapi

HBM_BYTES = 16e9
# (query heads, kv heads, head dim) of each model's attention
WIDTHS = {"qwen1.5-4b": (20, 20, 128), "qwen2-7b": (28, 4, 128)}
# the chip smoke load: 4 slots, prompt 512, budget 512 + 4 * 64
SLOTS, PROMPT, MAX_LEN, BLOCK = 4, 512, 768, 16
# the served decode step at the chip benchmark's serving sizes: layers kept
# (None: all), slots, max_len, and a bound on the step's HBM temporaries
DECODE_SERVED = {"qwen1.5-4b": (None, 8, 640, 64e6),
                 "qwen2-7b": (7, 32, 3200, None)}
_COPY = re.compile(r"= (\w+)\[([\d,]*)\]\{[^}]*\} copy\(")
_BYTES = {"bf16": 2, "f16": 2, "s8": 1, "u8": 1, "f8e4m3fn": 1, "pred": 1}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _on_chip(tree, one_chip):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)


def _compiled_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _fits(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used < HBM_BYTES, used
    return m


# ---------------------------------------------------------------------------
# the served steps of Qwen1.5-4B at full width
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def qwen4b(one_chip):
    cfg = get_config("qwen1.5-4b")
    api = mapi.build(cfg)
    params = _on_chip(jax.eval_shape(api.init, jax.random.PRNGKey(0)),
                      one_chip)
    return cfg, api, params


def test_qwen4b_prefill_compiles(qwen4b, one_chip):
    cfg, api, params = qwen4b
    batch = {"tokens": _spec(one_chip, (SLOTS, PROMPT), jnp.int32)}
    lens = _spec(one_chip, (SLOTS,), jnp.int32)
    step = jax.jit(lambda p, b, n: api.prefill(p, b, max_len=MAX_LEN,
                                               lens=n))
    m = _fits(step.lower(params, batch, lens).compile())
    slab = 2 * cfg.n_layers * SLOTS * MAX_LEN * cfg.n_kv_heads \
        * cfg.head_dim * 2
    assert m.output_size_in_bytes >= slab


def test_qwen4b_decode_paged_compiles(qwen4b, one_chip):
    cfg, api, params = qwen4b
    n_blocks = 1 + SLOTS * MAX_LEN // BLOCK
    pages = _spec(one_chip, (cfg.n_layers, n_blocks, cfg.n_kv_heads, BLOCK,
                             cfg.head_dim))
    pcache = {"k_pages": pages, "v_pages": pages,
              "tables": _spec(one_chip, (SLOTS, MAX_LEN // BLOCK), jnp.int32),
              "lens": _spec(one_chip, (SLOTS,), jnp.int32)}
    token = _spec(one_chip, (SLOTS, 1), jnp.int32)
    step = jax.jit(api.decode_paged, donate_argnums=(2,))
    m = _fits(step.lower(params, token, pcache).compile())
    assert m.alias_size_in_bytes >= 2 * pages.size * 2   # pool updated in place


def _copies(hlo: str, at_least: int) -> list:
    """Every ``copy`` in compiled HLO whose result holds ``at_least``
    bytes or more."""
    out = []
    for dt, dims in _COPY.findall(hlo):
        n = _BYTES.get(dt, 4)
        for d in filter(None, dims.split(",")):
            n *= int(d)
        if n >= at_least:
            out.append((dt, dims, n))
    return out


@pytest.mark.parametrize("model", sorted(DECODE_SERVED))
def test_decode_paged_reads_pool_in_place(model, one_chip):
    """The decode step reads and appends the pool in the order it is
    stored: no copy as large as one layer's K pool (a relayout of the pool
    or of a layer's slice), bounded temporaries, the pool aliased."""
    from repro.serving.kv_pool import init_pages
    layers, slots, max_len, temp_limit = DECODE_SERVED[model]
    cfg = get_config(model)
    if layers:
        cfg = cfg.replace(n_layers=layers)
    api = mapi.build(cfg)
    params = _on_chip(jax.eval_shape(api.init, jax.random.PRNGKey(0)),
                      one_chip)
    n_blocks = 1 + slots * max_len // BLOCK
    pcache = _on_chip(jax.eval_shape(
        lambda: init_pages(cfg, n_blocks, BLOCK)), one_chip)
    pcache["tables"] = _spec(one_chip, (slots, max_len // BLOCK), jnp.int32)
    pcache["lens"] = _spec(one_chip, (slots,), jnp.int32)
    token = _spec(one_chip, (slots, 1), jnp.int32)
    step = jax.jit(api.decode_paged, donate_argnums=(2,))
    compiled = step.lower(params, token, pcache).compile()
    m = _fits(compiled)
    pool = pcache["k_pages"]
    layer_bytes = pool.size // cfg.n_layers * pool.dtype.itemsize
    assert _copies(compiled.as_text(), layer_bytes) == []
    if temp_limit:
        assert m.temp_size_in_bytes < temp_limit, m.temp_size_in_bytes
    assert m.alias_size_in_bytes >= 2 * pool.size * pool.dtype.itemsize


# ---------------------------------------------------------------------------
# kernels at real widths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("model", sorted(WIDTHS))
@pytest.mark.parametrize("variant", ["dense", "int8", "sparse"])
def test_paged_decode_compiles(model, variant, one_chip):
    Hq, Hkv, D = WIDTHS[model]
    n, t = 1 + SLOTS * MAX_LEN // BLOCK, MAX_LEN // BLOCK
    q = _spec(one_chip, (SLOTS, Hq, D))
    tables = _spec(one_chip, (SLOTS, t), jnp.int32)
    cur = _spec(one_chip, (SLOTS,), jnp.int32)
    if variant == "int8":
        pages = _spec(one_chip, (n, Hkv, BLOCK, D), jnp.int8)
        scales = _spec(one_chip, (n, Hkv), jnp.float32)
        _compiled_kernel(
            lambda *a: pa_ops.paged_decode_quant(*a, interpret=False),
            q, pages, pages, scales, scales, tables, cur)
        return
    pages = _spec(one_chip, (n, Hkv, BLOCK, D))
    if variant == "sparse":
        fn = lambda *a: pa_ops.paged_decode_sparse(  # noqa: E731
            *a, threshold=0.05, interpret=False)
    else:
        fn = lambda *a: pa_ops.paged_decode(  # noqa: E731
            *a, window=256, interpret=False)
    _compiled_kernel(fn, q, pages, pages, tables, cur)


@pytest.mark.parametrize("model", sorted(WIDTHS))
def test_flash_kernels_compile(model, one_chip):
    Hq, Hkv, D = WIDTHS[model]
    _compiled_kernel(
        lambda q, k, v: fa_ops.flash_attention(q, k, v, window=256,
                                               interpret=False),
        _spec(one_chip, (1, PROMPT, Hq, D)),
        _spec(one_chip, (1, PROMPT, Hkv, D)),
        _spec(one_chip, (1, PROMPT, Hkv, D)))
    _compiled_kernel(
        lambda q, k, v, c: fd_ops.flash_decode(q, k, v, c, bk=256,
                                               interpret=False),
        _spec(one_chip, (SLOTS, Hq, D)),
        _spec(one_chip, (SLOTS, MAX_LEN, Hkv, D)),
        _spec(one_chip, (SLOTS, MAX_LEN, Hkv, D)),
        _spec(one_chip, (), jnp.int32))


def test_matmul_compiles(one_chip):
    cfg = get_config("qwen1.5-4b")
    _compiled_kernel(lambda a, b: mm_ops.matmul(a, b, interpret=False),
                     _spec(one_chip, (PROMPT, cfg.d_model)),
                     _spec(one_chip, (cfg.d_model, cfg.d_ff)))


# ResNet-50 / GoogLeNet convolutions: (H, C, K, k, stride)
@pytest.mark.parametrize("H,C,K,k,stride", [
    (56, 64, 64, 3, 1),       # ResNet-50 stage 1, 3x3
    (56, 256, 128, 1, 2),     # stage 2 entry, strided 1x1
    (56, 128, 128, 3, 2),     # strided 3x3
    (7, 512, 512, 3, 1),      # stage 4, 3x3 (7 rows: untiled)
    (56, 64, 192, 3, 1),      # GoogLeNet 3x3, 192 outputs (not lane-tiled)
])
def test_conv2d_compiles(H, C, K, k, stride, one_chip):
    _compiled_kernel(
        lambda x, w: conv_ops.conv2d(x, w, stride=stride, interpret=False),
        _spec(one_chip, (1, H, H, C)), _spec(one_chip, (k, k, C, K)))


# ---------------------------------------------------------------------------
# refusals: shapes a kernel cannot take are turned away before Mosaic
# ---------------------------------------------------------------------------


def test_conv2d_refuses_shapes_over_vmem(one_chip):
    """The ResNet-50 stem (224x224x3, 7x7 stride 2) pads its 3 channels to
    128 lanes: its input panel alone overflows VMEM."""
    with pytest.raises(ValueError, match="VMEM"):
        jax.jit(lambda x, w: conv_ops.conv2d(x, w, stride=2,
                                             interpret=False)).lower(
            _spec(one_chip, (1, 224, 224, 3)),
            _spec(one_chip, (7, 7, 3, 64)))


def test_flash_decode_refuses_untiled_cache(one_chip):
    with pytest.raises(ValueError, match="does not tile"):
        jax.jit(lambda q, k, v, c: fd_ops.flash_decode(
            q, k, v, c, bk=512, interpret=False)).lower(
            _spec(one_chip, (SLOTS, 20, 128)),
            _spec(one_chip, (SLOTS, MAX_LEN, 20, 128)),
            _spec(one_chip, (SLOTS, MAX_LEN, 20, 128)),
            _spec(one_chip, (), jnp.int32))
