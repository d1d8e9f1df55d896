"""Compile the serving kernels for a described TPU v5e chip, no chip needed.

Interpret mode accepts block shapes and ops that Mosaic refuses, so the
kernel-vs-reference tests alone cannot say whether the decode-on-read path
runs on the chip.  Each test here lowers one kernel at paper-edge full
widths (B=8, W=2048, 12 query / 4 KV heads, hd=64, page size 16; the
paged decode attention at both benchmark cells' widths) with
``interpret=False`` for one chip of a described ``v5e:2x2`` topology and
asserts that the compiled program holds the Mosaic kernel.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and under pytest-xdist every
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.formats import POSIT4_1, POSIT8_2, POSIT16_2
from repro.kernels import kv_cache as kvk
from repro.kernels import paged_kv as pkv
from repro.kernels.posit_decode import posit_decode
from repro.kernels.posit_encode import posit_encode
from repro.kernels.posit_matmul import posit_matmul

FMTS = [pytest.param(POSIT8_2, False, id="posit8"),
        pytest.param(POSIT16_2, False, id="posit16"),
        pytest.param(POSIT4_1, True, id="posit4")]
B, W, NH, NKV, HD, PS = 8, 2048, 12, 4, 64, 16
T = 5                                   # speculative chunk: gamma 4 + 1
PMAX = W // PS
POOL_ROWS = (1 + B * PMAX) * PS         # trash page + a full reservation


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _ring_args(one_chip, fmt, packed):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    dc = kvk.code_channels(HD, fmt, packed)
    codes = s((B, W, NKV, dc), fmt.storage_dtype)
    scale = s((B, W, NKV), jnp.float32)
    return s, codes, scale


def _pool_args(one_chip, fmt, packed):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    dc = kvk.code_channels(HD, fmt, packed)
    codes = s((POOL_ROWS, NKV, dc), fmt.storage_dtype)
    scale = s((POOL_ROWS, NKV), jnp.float32)
    return s, codes, scale


@pytest.mark.parametrize("fmt,packed", FMTS)
def test_kv_append_rows_compiles(one_chip, fmt, packed):
    s, codes, scale = _ring_args(one_chip, fmt, packed)
    new = s((B, T, NKV, HD), jnp.float32)
    fn = lambda kc, ks, vc, vs, kn, vn, pos: kvk.kv_append_rows(
        kc, ks, vc, vs, kn, vn, pos, fmt, packed=packed, interpret=False)
    _compile(fn, codes, scale, codes, scale, new, new,
             s((B,), jnp.int32))


@pytest.mark.parametrize("fmt,packed", FMTS)
def test_decode_attention_compiles(one_chip, fmt, packed):
    s, codes, scale = _ring_args(one_chip, fmt, packed)
    fn = lambda q, kc, ks, vc, vs, ln: kvk.decode_attention(
        q, kc, ks, vc, vs, ln, fmt, packed=packed, interpret=False)
    _compile(fn, s((B, 1, NH, HD), jnp.bfloat16), codes, scale, codes,
             scale, s((B,), jnp.int32))


@pytest.mark.parametrize("fmt,packed", FMTS)
def test_paged_kv_append_rows_compiles(one_chip, fmt, packed):
    s, codes, scale = _pool_args(one_chip, fmt, packed)
    new = s((B, T, NKV, HD), jnp.float32)
    fn = lambda kc, ks, vc, vs, kn, vn, dst: pkv.paged_kv_append_rows(
        kc, ks, vc, vs, kn, vn, dst, fmt, packed=packed, interpret=False)
    _compile(fn, codes, scale, codes, scale, new, new,
             s((B, T), jnp.int32))


# the benchmark's two paged cells: paper-edge (12 / 4 heads of 64) and
# Granite-3.0-8B (32 / 8 heads of 128), 16 slots of 256 16-row pages
ATTN_WIDTHS = [pytest.param(12, 4, 64, id="edge"),
               pytest.param(32, 8, 128, id="granite")]


@pytest.mark.parametrize("nh,nkv,hd", ATTN_WIDTHS)
@pytest.mark.parametrize("fmt,packed", FMTS)
def test_paged_decode_attention_compiles(one_chip, fmt, packed, nh, nkv, hd):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    b, pmax = 16, 256
    rows = (1 + b * pmax) * PS
    codes = s((rows, nkv, kvk.code_channels(hd, fmt, packed)),
              fmt.storage_dtype)
    scale = s((rows, nkv), jnp.float32)
    fn = lambda q, kc, ks, vc, vs, tbl, ln: pkv.paged_decode_attention(
        q, kc, ks, vc, vs, tbl, ln, fmt, page_size=PS, packed=packed,
        interpret=False)
    _compile(fn, s((b, 1, nh, hd), jnp.bfloat16), codes, scale, codes,
             scale, s((b, pmax), jnp.int32), s((b,), jnp.int32))


def test_posit_matmul_compiles(one_chip):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fn = lambda x, w, sc: posit_matmul(x, w, POSIT8_2, sc, interpret=False)
    _compile(fn, s((B, 768), jnp.bfloat16), s((768, 2048), jnp.uint8),
             s((2048,), jnp.float32))


@pytest.mark.parametrize("fmt", [POSIT8_2, POSIT16_2], ids=["posit8",
                                                            "posit16"])
def test_posit_codec_compiles(one_chip, fmt):
    """The standalone codec kernels share ``decode_tile``/``encode_tile``
    with every KV kernel: a (768, 2048) weight-sized tile each way."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(lambda c: posit_decode(c, fmt, interpret=False),
             s((768, 2048), fmt.storage_dtype))
    _compile(lambda x: posit_encode(x, fmt, interpret=False),
             s((768, 2048), jnp.float32))
