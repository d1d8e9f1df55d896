"""Posit-packed KV cache: kernel-vs-reference bit-exactness, round-trip
error bounds per format, and engine-level greedy-decode equivalence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import posit
from repro.core.formats import POSIT4_1, POSIT8_2, POSIT16_2
from repro.core.transprecision import BF16, KV_FORMATS, kv_storage
from repro.kernels import kv_cache as kvk
from repro.models import lm
from repro.models.serve_model import decode_step, prefill
from repro.serve.engine import Request, ServeConfig, ServingEngine

FMTS = [("posit16", POSIT16_2, False), ("posit8", POSIT8_2, False),
        ("posit4", POSIT4_1, True)]


# ---------------------------------------------------------------------------
# Codec round-trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,fmt,packed", FMTS, ids=lambda x: str(x))
def test_kv_roundtrip_within_posit_ulp(name, fmt, packed):
    """encode->decode of scaled rows stays within one posit ULP per value:
    the per-row pow2 scale is exact, so the only error is the posit RNE,
    bounded by useed^|k| taper — check against the direct posit round-trip
    of the scaled value (which IS the ULP-correct answer)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 0.3, (4, 6, 16)), jnp.float32)
    codes, scale = kvk.encode_kv_rows(x, fmt, packed)
    back = kvk.decode_kv_rows(codes, scale, fmt, packed)
    # bit-exact vs the scalar posit codec applied to x/scale
    want = posit.decode_to_f32(
        posit.encode_f32(x / scale, fmt), fmt) * scale
    np.testing.assert_array_equal(np.asarray(back), np.asarray(want))
    # and the relative error is format-taper bounded near the row scale
    rel = np.abs(np.asarray(back) - np.asarray(x)) / (np.abs(x) + 1e-6)
    med = float(np.median(rel))
    assert med < {"posit16": 2e-4, "posit8": 0.05, "posit4": 0.5}[name], med


def test_nibble_pack_roundtrip():
    rng = np.random.default_rng(1)
    codes = rng.integers(0, 16, (3, 5, 8)).astype(np.uint8)
    packed = kvk.pack_nibbles(jnp.asarray(codes))
    assert packed.shape == (3, 5, 4)
    np.testing.assert_array_equal(
        np.asarray(kvk.unpack_nibbles(packed)), codes)


# ---------------------------------------------------------------------------
# Pallas kernels vs pure-jnp oracles (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,fmt,packed", FMTS, ids=lambda x: str(x))
def test_kv_append_kernel_bit_exact(name, fmt, packed):
    rng = np.random.default_rng(2)
    b, w, h, hd = 2, 8, 3, 16
    dc = kvk.code_channels(hd, fmt, packed)
    kc = jnp.zeros((b, w, h, dc), fmt.storage_dtype)
    ks = jnp.ones((b, w, h), jnp.float32)
    vc, vs = kc, ks
    for pos in (0, 3, 9):   # incl. ring wrap
        kn = jnp.asarray(rng.normal(0, 0.5, (b, 1, h, hd)), jnp.float32)
        vn = jnp.asarray(rng.normal(0, 2.0, (b, 1, h, hd)), jnp.float32)
        got = kvk.kv_append(kc, ks, vc, vs, kn, vn, pos, fmt,
                            packed=packed, interpret=True)
        want = kvk.kv_append_ref(kc, ks, vc, vs, kn, vn, pos, fmt, packed)
        for g, wv in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(wv))
        kc, ks, vc, vs = got


@pytest.mark.parametrize("name,fmt,packed", FMTS, ids=lambda x: str(x))
@pytest.mark.parametrize("cache_len", [1, 5, 16])
def test_fused_decode_attention_matches_ref(name, fmt, packed, cache_len):
    rng = np.random.default_rng(3)
    b, w, nkv, grp, hd = 2, 16, 2, 3, 8
    kf = rng.normal(0, 1, (b, w, nkv, hd)).astype(np.float32)
    vf = rng.normal(0, 1, (b, w, nkv, hd)).astype(np.float32)
    kc, ks = kvk.encode_kv_rows(jnp.asarray(kf), fmt, packed)
    vc, vs = kvk.encode_kv_rows(jnp.asarray(vf), fmt, packed)
    ks, vs = ks[..., 0], vs[..., 0]
    q = jnp.asarray(rng.normal(0, 1, (b, 1, nkv * grp, hd)), jnp.float32)
    got = kvk.decode_attention(q, kc, ks, vc, vs, cache_len, fmt,
                               packed=packed, block_w=4, interpret=True)
    want = kvk.decode_attention_ref(q, kc, ks, vc, vs, cache_len, fmt,
                                    packed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# KV storage resolution + cache footprint
# ---------------------------------------------------------------------------

def test_kv_storage_resolution():
    assert kv_storage(BF16) is None
    p8 = dataclasses.replace(BF16, kv_format="posit8", name="p8")
    spec = kv_storage(p8)
    assert spec.is_posit and spec.fmt.bits == 8 and not spec.packed
    p4 = dataclasses.replace(BF16, kv_format="posit4", name="p4")
    assert kv_storage(p4).packed
    from repro.core.transprecision import SERVE_P16
    legacy = kv_storage(SERVE_P16)
    assert legacy.is_posit and legacy.fmt.bits == 16
    with pytest.raises(KeyError):
        kv_storage(dataclasses.replace(BF16, kv_format="fp7", name="x"))
    # amortized bytes/value at hd=64: posit8 ~0.53x bf16, posit4 <=0.3x
    bf = KV_FORMATS["bf16"].bytes_per_value(64)
    assert KV_FORMATS["posit8"].bytes_per_value(64) / bf < 0.54
    assert KV_FORMATS["posit4"].bytes_per_value(64) / bf <= 0.3


# ---------------------------------------------------------------------------
# Engine-level greedy equivalence
# ---------------------------------------------------------------------------

def _serve_tokens(cfg, params, prompts, kv_format, max_new=8):
    eng = ServingEngine(cfg, params,
                        ServeConfig(max_batch=2, max_len=64,
                                    kv_format=kv_format))
    reqs = [Request(uid=i, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    stats = eng.serve(reqs)
    return [r.out_tokens for r in reqs], stats


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_config("paper-edge", smoke=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, int(rng.integers(4, 12)))
               for _ in range(4)]
    return cfg, params, prompts


def _teacher_forced_logits(cfg, params, prompt, cont, kv_format):
    """Prefill + one decode step per ``cont`` token, fed the same tokens
    whatever the KV format: (1 + len(cont), vocab) f32 logits."""
    pol = dataclasses.replace(BF16, kv_format=kv_format, name=kv_format)
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompt)[None]},
                            cfg, 64, pol)
    out = [logits]
    for t in cont:
        logits, cache = decode_step(params, cache,
                                    jnp.asarray([[t]], jnp.int32), cfg, pol)
        out.append(logits)
    return np.stack([np.asarray(o[0, :cfg.vocab], np.float32) for o in out])


def test_greedy_decode_bf16_equals_f32(smoke_model):
    """A bf16 KV cache tracks the f32 one in logits, not in sampled tokens:
    on random weights the top-2 logit gap is often one bf16 ulp, so a
    greedy token may flip on rounding alone.  Each bf16 K/V value carries
    a relative error <= 2^-9 and the smoke model's logits are themselves
    bf16 (spacing 2^-8 relative), so every logit must stay within four
    such spacings of the largest f32 logit (2^-6 * max|logit|)."""
    cfg, params, prompts = smoke_model
    rng = np.random.default_rng(4)
    for prompt in prompts:
        cont = rng.integers(0, cfg.vocab, 8)
        want = _teacher_forced_logits(cfg, params, prompt, cont, "f32")
        got = _teacher_forced_logits(cfg, params, prompt, cont, "bf16")
        tol = 2.0 ** -6 * np.abs(want).max()
        np.testing.assert_allclose(got, want, rtol=0, atol=tol)
    bytes_of = lambda kvf: ServingEngine(cfg, params, ServeConfig(
        max_batch=2, max_len=64, kv_format=kvf)).kv_cache_bytes()
    assert bytes_of("bf16") < bytes_of("f32")


def test_greedy_decode_posit16_equals_f32(smoke_model):
    """Acceptance: posit16 KV matches the f32 cache on the quickstart-style
    prompt set, at half the f32 cache footprint (codes) + scales."""
    cfg, params, prompts = smoke_model
    t_f32, s32 = _serve_tokens(cfg, params, prompts, "f32")
    t_p16, s16 = _serve_tokens(cfg, params, prompts, "posit16")
    assert t_p16 == t_f32
    assert s16["kv_cache_bytes"] < 0.6 * s32["kv_cache_bytes"]


def test_engine_runs_posit8_and_posit4(smoke_model):
    cfg, params, prompts = smoke_model
    for kvf in ("posit8", "posit4"):
        toks, stats = _serve_tokens(cfg, params, prompts, kvf)
        assert all(len(t) > 0 for t in toks)
        assert stats["tokens"] > 0
