"""The plain reference: its posit rounding agrees with the program's codec
code for code, and its model agrees with the program's float32 forward
pass where both keep K/V at full precision.  (The reference itself imports
nothing of the program; these tests hold the two side by side.)"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reference as R
import weights as W
from harness import BENCH_DIR
from test_bench_correct import CONFIGS

FX = BENCH_DIR / "tests" / "fixtures"
TINY = json.loads((FX / "tiny.p8-paged.json").read_text())


def test_posit_tables_from_the_definition():
    v, b = R.posit_tables(8, 2)
    assert len(v) == 127 and v[0] == 2.0 ** -24 and v[-1] == 2.0 ** 24
    assert 1.0 in v and 1.125 in v          # 1 + 1/8: three fraction bits
    # between 2^20 and maxpos 2^24 the bit-string midpoint is 2^22
    assert b[-1] == 2.0 ** 22
    v4, _ = R.posit_tables(4, 1)
    assert list(v4) == [0.0625, 0.25, 0.5, 1.0, 2.0, 4.0, 16.0]


@pytest.mark.parametrize("fmt,prog", [("posit8_2", "posit8_2"),
                                      ("posit4_1", "posit4_1"),
                                      ("posit16_2", "posit16_2")])
def test_rounding_matches_the_program_codec(fmt, prog):
    from repro.core import posit
    from repro.core.formats import get
    n, es = R.POSIT_FORMATS[fmt]
    v, b = R.posit_tables(n, es)
    rng = np.random.default_rng(0)
    pts = np.concatenate([v, b, np.nextafter(b, 0), np.nextafter(b, np.inf),
                          v * 1.7, [1e-30, 3e-9, 1e9, 7e30],
                          np.exp(rng.uniform(-60, 60, 20000))])
    pts = np.concatenate([pts, -pts, [0.0]]).astype(np.float32)
    f = get(prog)
    want = posit.decode_to_f32(posit.encode_f32(jnp.asarray(pts), f), f)
    got = R.round_posit(jnp.asarray(pts), fmt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("fmt", ["posit4_1", "posit8_2", "posit16_2"])
def test_rounding_without_a_gather_matches_the_table_search(fmt):
    """``round_posit`` (evenly spaced binades by arithmetic, the codes near
    minpos and maxpos by counting boundaries) gives the binary search's
    answer on every value, every boundary and its neighbours, and far
    outside the range."""
    n, es = R.POSIT_FORMATS[fmt]
    v, b = R.posit_tables(n, es)
    rng = np.random.default_rng(1)
    pts = np.concatenate([v, b, np.nextafter(b, 0), np.nextafter(b, np.inf),
                          np.nextafter(v, 0), np.nextafter(v, np.inf),
                          v * 1.7, v * 0.77, [1e-45, 1e-30, 1e30, 3e38],
                          np.exp(rng.uniform(-90, 85, 50000)),
                          rng.normal(size=20000)])
    pts = np.concatenate([pts, -pts, [0.0]]).astype(np.float32)
    want = R.round_posit_table(jnp.asarray(pts), fmt)
    got = jax.jit(lambda x: R.round_posit(x, fmt))(jnp.asarray(pts))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_served_gaps_on_the_device_match_the_logits():
    conf = TINY
    w = W.make_weights(conf, 8)
    rng = np.random.default_rng(5)
    seqs = [rng.integers(0, 256, n).astype(np.int32) for n in (50, 31)]
    # the served tokens end each sequence, and one more follows it
    served = [rng.integers(0, 256, len(q) - n + 1).astype(np.int32)
              for q, n in zip(seqs, (40, 20))]
    ref = R.make_reference(conf)
    full = ref(w, seqs, [40, 20])
    gaps = ref(w, seqs, [40, 20], served=served, width=512)
    for lg, g, s, n in zip(full, gaps, served, (40, 20)):
        np.testing.assert_allclose(g, R.served_gaps(lg, n, s), atol=1e-5)


def test_weight_rounding_matches_the_program_policy():
    from repro.core.transprecision import get_policy
    w = jax.random.normal(jax.random.PRNGKey(1), (96, 40)) * 0.05
    w = w.at[3, :].set(0.0)
    pol = get_policy("paper_edge_p8")
    want = pol.quantize_weight(w, "mlp_weights")
    np.testing.assert_array_equal(np.asarray(R.quantize_weight(w, "posit8_2")),
                                  np.asarray(want))


def test_kv_rounding_matches_the_program_codec():
    from repro.core.formats import POSIT8_2
    from repro.kernels.kv_cache import decode_kv_rows, encode_kv_rows
    x = jax.random.normal(jax.random.PRNGKey(2), (5, 7, 2, 16)) * 3.0
    codes, scale = encode_kv_rows(x, POSIT8_2)
    want = decode_kv_rows(codes, scale, POSIT8_2)
    np.testing.assert_array_equal(np.asarray(R.quantize_kv_rows(x, "posit8")),
                                  np.asarray(want))


@pytest.mark.parametrize("fixture", CONFIGS)
def test_model_matches_the_program_forward(fixture):
    """With every query before ``n_prompt`` (full-precision K/V), the
    family's reference is the program's float32 ``lm.forward`` under the
    policy, on its weights: for the dense family with a head of its own
    and with the head tied to the embedding."""
    from repro.core.transprecision import get_policy
    from repro.models import lm
    conf = json.loads((FX / f"{fixture}.json").read_text())
    family = harness.family_of(conf)
    vocab = conf["model"]["vocab_size"]
    w = family.make_weights(conf, 5)
    cfg = dataclasses.replace(family.program_cfg(conf), dtype_name="float32")
    w32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)
    toks = np.random.default_rng(3).integers(0, vocab, (2, 40)) \
        .astype(np.int32)
    policy = get_policy(conf["serving"]["policy"])
    with jax.default_matmul_precision("highest"):
        want = lm.forward(w32, {"tokens": jnp.asarray(toks)}, cfg,
                          policy)[0][..., :vocab]
    got = family.make_reference(conf)(w, list(toks), [40, 40])
    for g, wt in zip(got, np.asarray(want)):
        np.testing.assert_allclose(g, wt, rtol=2e-4, atol=2e-4)


def test_cached_kv_changes_later_positions_only():
    conf = TINY
    w = W.make_weights(conf, 6)
    seq = np.random.default_rng(4).integers(0, 256, 50).astype(np.int32)
    ref = R.make_reference(conf)
    full, cached = ref(w, [seq, seq], [50, 20])
    np.testing.assert_array_equal(full[:20], cached[:20])
    assert np.abs(full[20:] - cached[20:]).max() > 0


def test_served_gaps():
    lg = np.array([[0.0, 1.0, 3.0], [2.0, 0.5, 0.0], [1.0, 1.0, 1.5]])
    # prompt of 2 tokens: positions 1 and 2 produced the served tokens
    gaps = R.served_gaps(lg, 2, np.array([1, 2]))
    np.testing.assert_allclose(gaps, [1.5, 0.0])
