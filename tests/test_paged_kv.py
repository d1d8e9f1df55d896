"""Paged posit KV cache: block allocator semantics, paged Pallas kernels
vs pure-jnp oracles, ring/paged greedy equivalence, and the continuous-
batching engine with true per-slot positions (mixed prompt lengths, slot
reuse after EOS, head-of-line admission)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.formats import POSIT4_1, POSIT8_2, POSIT16_2
from repro.core.transprecision import BF16
from repro.kernels import kv_cache as kvk
from repro.kernels import paged_kv as pkv
from repro.models import lm
from repro.models.serve_model import decode_step, init_cache, prefill
from repro.serve.engine import Request, ServeConfig, ServingEngine
from repro.serve.paged import PageAllocator, SlotPages, pages_for

FMTS = [("posit16", POSIT16_2, False), ("posit8", POSIT8_2, False),
        ("posit4", POSIT4_1, True)]


# ---------------------------------------------------------------------------
# Block allocator
# ---------------------------------------------------------------------------

def test_allocator_alloc_free_reuse():
    a = PageAllocator(num_pages=5, page_size=4)
    assert a.num_free == 4 and a.live_pages == 0      # page 0 reserved
    p1 = a.alloc(2)
    p2 = a.alloc(2)
    assert a.alloc(1) is None                          # exhausted
    assert sorted(p1 + p2) == [1, 2, 3, 4]
    assert 0 not in p1 + p2                            # trash never handed out
    a.free(p1)
    assert a.num_free == 2 and a.live_pages == 2
    p3 = a.alloc(2)                                    # freed pages come back
    assert sorted(p3) == sorted(p1)
    with pytest.raises(ValueError):
        a.free(p1 + p1)                                # double free detected


def test_allocator_fork_refcounts():
    a = PageAllocator(num_pages=4, page_size=2)
    p = a.alloc(2)
    shared = a.fork(p)
    assert shared == p and a.ref_count(p[0]) == 2
    a.free(p)                                          # first owner drops
    assert a.num_free == 1                             # still shared
    a.free(shared)
    assert a.num_free == 3                             # now returned


def test_slot_pages_growth_and_table_row():
    sp = SlotPages(page_size=4, pages=[3, 1])
    assert sp.pages_needed(8) == 0
    assert sp.pages_needed(9) == 1
    row = sp.table_row(5)
    assert row.tolist() == [3, 1, 0, 0, 0]
    assert pages_for(0, 4) == 0 and pages_for(1, 4) == 1 and pages_for(9, 4) == 3


def test_flat_dst_rows_clamps_idle_slots():
    table = jnp.asarray([[2, 3], [0, 0]], jnp.int32)
    rows = pkv.flat_dst_rows(table, jnp.asarray([5, 99]), page_size=4)
    # slot 0: page 3 (logical 1), offset 1; slot 1: clamped to trash page
    assert rows.tolist() == [3 * 4 + 1, 0 * 4 + 3]


# ---------------------------------------------------------------------------
# Paged Pallas kernels vs pure-jnp oracles (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,fmt,packed", FMTS, ids=lambda x: str(x))
def test_paged_append_kernel_bit_exact(name, fmt, packed):
    rng = np.random.default_rng(2)
    b, nkv, hd, ps, npages = 3, 2, 16, 4, 7
    dc = kvk.code_channels(hd, fmt, packed)
    kc = jnp.zeros((npages * ps, nkv, dc), fmt.storage_dtype)
    ks = jnp.ones((npages * ps, nkv), jnp.float32)
    vc, vs = kc, ks
    table = jnp.asarray([[1, 2, 0], [3, 4, 0], [5, 6, 0]], jnp.int32)
    for pos in ([0, 1, 2], [3, 4, 7], [5, 6, 4]):     # incl. 2nd-page writes
        kn = jnp.asarray(rng.normal(0, .5, (b, 1, nkv, hd)), jnp.float32)
        vn = jnp.asarray(rng.normal(0, 2., (b, 1, nkv, hd)), jnp.float32)
        dst = pkv.flat_dst_rows(table, jnp.asarray(pos), ps)
        got = pkv.paged_kv_append(kc, ks, vc, vs, kn, vn, dst, fmt,
                                  packed=packed, interpret=True)
        want = pkv.paged_kv_append_ref(kc, ks, vc, vs, kn, vn, dst, fmt,
                                       packed)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        kc, ks, vc, vs = got


# (page size, Pmax, lengths, table): the first three cases walk pages of 4
# rows, one block each; at 16-row pages a block holds 8 pages (128 rows)
ATTN_CASES = {
    "ps4-len1": (4, 3, (1, 1, 1), "fixed"),
    "ps4-mixed": (4, 3, (6, 12, 11), "fixed"),
    "ps4-full": (4, 3, (3, 8, 12), "fixed"),
    "len1": (16, 20, (1, 1, 1), "shuffled"),
    "page-boundary": (16, 20, (16, 32, 48), "shuffled"),
    "block-boundary": (16, 20, (128, 256, 127), "shuffled"),
    "several-blocks": (16, 20, (300, 129, 257), "shuffled"),
    "all-trash-slot": (16, 20, (40, 100, 5), "trash"),
    "pmax-not-multiple": (16, 20, (320, 319, 260), "shuffled"),
    "past-the-table": (16, 20, (5000, 321, 64), "shuffled"),
    "pmax-below-block": (16, 3, (48, 17, 1), "shuffled"),
}


def _attn_pool(rng, fmt, packed, b, nkv, hd, ps, pmax, layout):
    """A random posit pool and a page table of ``layout``: ``fixed`` (the
    hand-written 3-page table), ``shuffled`` (physical pages in a random,
    non-contiguous order) or ``trash`` (shuffled, slot 1 all trash)."""
    npages = 1 + b * pmax
    rows = npages * ps
    kf = rng.normal(0, 1, (rows, nkv, hd)).astype(np.float32)
    vf = rng.normal(0, 1, (rows, nkv, hd)).astype(np.float32)
    kc, ks = kvk.encode_kv_rows(jnp.asarray(kf), fmt, packed)
    vc, vs = kvk.encode_kv_rows(jnp.asarray(vf), fmt, packed)
    if layout == "fixed":
        table = np.asarray([[1, 2, 0], [3, 4, 5], [6, 1, 2]])
    else:
        table = 1 + rng.permutation(b * pmax).reshape(b, pmax)
        if layout == "trash":
            table[1] = 0
    return kc, ks[..., 0], vc, vs[..., 0], jnp.asarray(table, jnp.int32)


@pytest.mark.parametrize("name,fmt,packed", FMTS, ids=lambda x: str(x))
@pytest.mark.parametrize("case", list(ATTN_CASES))
def test_paged_decode_attention_matches_ref(name, fmt, packed, case):
    ps, pmax, lens, layout = ATTN_CASES[case]
    rng = np.random.default_rng(3)
    b, nkv, grp, hd = 3, 2, 2, 8
    kc, ks, vc, vs, table = _attn_pool(rng, fmt, packed, b, nkv, hd, ps,
                                       pmax, layout)
    q = jnp.asarray(rng.normal(0, 1, (b, 1, nkv * grp, hd)), jnp.float32)
    seq_lens = jnp.asarray(lens, jnp.int32)
    got = pkv.paged_decode_attention(q, kc, ks, vc, vs, table, seq_lens,
                                     fmt, page_size=ps, packed=packed,
                                     interpret=True)
    want = pkv.paged_decode_attention_ref(q, kc, ks, vc, vs, table,
                                          seq_lens, fmt, page_size=ps,
                                          packed=packed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("name,fmt,packed", FMTS, ids=lambda x: str(x))
def test_paged_decode_attention_empty_slot_is_zero(name, fmt, packed):
    """A slot of length 0 (an idle slot) reads no page and computes
    nothing, yet its output is written: exact zeros, whatever the VMEM
    buffers held from the slot before; its neighbours still match the
    reference."""
    rng = np.random.default_rng(4)
    b, nkv, grp, hd, ps, pmax = 3, 2, 2, 8, 16, 20
    kc, ks, vc, vs, table = _attn_pool(rng, fmt, packed, b, nkv, hd, ps,
                                       pmax, "shuffled")
    q = jnp.asarray(rng.normal(0, 1, (b, 1, nkv * grp, hd)), jnp.float32)
    seq_lens = jnp.asarray([200, 0, 37], jnp.int32)
    got = np.asarray(pkv.paged_decode_attention(
        q, kc, ks, vc, vs, table, seq_lens, fmt, page_size=ps,
        packed=packed, interpret=True))
    want = np.asarray(pkv.paged_decode_attention_ref(
        q, kc, ks, vc, vs, table, seq_lens, fmt, page_size=ps,
        packed=packed))
    np.testing.assert_array_equal(got[1], 0.0)
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], rtol=1e-5,
                               atol=1e-5)


def test_pages_per_block_covers_about_128_rows():
    assert pkv.pages_per_block(16, 256) == 8
    assert pkv.pages_per_block(16, 3) == 3            # never past Pmax
    assert pkv.pages_per_block(4, 256) == 32
    assert pkv.pages_per_block(256, 16) == 1


def test_gather_pages_logical_order():
    pool = jnp.arange(4 * 2 * 3, dtype=jnp.float32).reshape(4 * 2, 3)
    table = jnp.asarray([[2, 1], [3, 0]], jnp.int32)
    out = pkv.gather_pages(pool, table, page_size=2)
    np.testing.assert_array_equal(np.asarray(out[0, :2]), np.asarray(pool[4:6]))
    np.testing.assert_array_equal(np.asarray(out[0, 2:]), np.asarray(pool[2:4]))
    np.testing.assert_array_equal(np.asarray(out[1, :2]), np.asarray(pool[6:8]))


# ---------------------------------------------------------------------------
# Ring/paged equivalence (standalone model level) + engine batching
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_config("paper-edge", smoke=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n) for n in (4, 11, 7)]
    return cfg, params, prompts


def _greedy_single(cfg, params, prompt, policy, max_len, max_new):
    """Single-sequence greedy decode: the per-request ground truth."""
    tokens = jnp.asarray(prompt, jnp.int32)[None, :]
    logits, cache = prefill(params, {"tokens": tokens}, cfg, max_len, policy)
    out = [int(np.argmax(np.asarray(logits)[0][: cfg.vocab]))]
    for _ in range(max_new - 1):
        logits, cache = decode_step(
            params, cache, jnp.asarray([[out[-1]]], jnp.int32), cfg, policy)
        out.append(int(np.argmax(np.asarray(logits)[0][: cfg.vocab])))
    return out


@pytest.mark.parametrize("kvf", ["posit16", "posit8"])
def test_paged_matches_ring_standalone(smoke_model, kvf):
    """Acceptance: paged greedy decode == ring greedy decode, token for
    token, for the posit formats (jnp-reference backend)."""
    cfg, params, prompts = smoke_model
    ring = dataclasses.replace(BF16, kv_format=kvf, name=f"tr_{kvf}")
    paged = dataclasses.replace(BF16, kv_format=kvf, kv_layout="paged",
                                kv_page_size=4, name=f"tp_{kvf}")
    t_ring = _greedy_single(cfg, params, prompts[1], ring, 32, 6)
    t_paged = _greedy_single(cfg, params, prompts[1], paged, 32, 6)
    assert t_ring == t_paged


@pytest.mark.parametrize("layout", ["ring", "paged"])
@pytest.mark.parametrize("kvf", ["f32", "posit16"])
def test_engine_mixed_lengths_match_single_sequence(smoke_model, kvf, layout):
    """Continuous batching with heterogeneous prompt lengths and slot
    reuse: every request's greedy stream must equal its single-sequence
    decode (true per-slot positions; the old shared-pos engine could
    not pass this)."""
    cfg, params, prompts = smoke_model
    policy = dataclasses.replace(BF16, kv_format=kvf, name=f"te_{kvf}")
    refs = [_greedy_single(cfg, params, p, policy, 32, 5) for p in prompts]
    eng = ServingEngine(cfg, params,
                        ServeConfig(max_batch=2, max_len=32, kv_format=kvf,
                                    kv_layout=layout, page_size=4))
    reqs = [Request(uid=i, prompt=p, max_new=5)
            for i, p in enumerate(prompts)]
    eng.serve(reqs)
    assert [r.out_tokens for r in reqs] == refs


def test_engine_posit8_paged_runs(smoke_model):
    cfg, params, prompts = smoke_model
    eng = ServingEngine(cfg, params,
                        ServeConfig(max_batch=2, max_len=32,
                                    kv_format="posit8", kv_layout="paged",
                                    page_size=4))
    reqs = [Request(uid=0, prompt=prompts[0], max_new=4)]
    stats = eng.serve(reqs)
    assert len(reqs[0].out_tokens) == 4 and stats["tokens"] > 0


def test_engine_slot_reuse_after_eos_frees_pages(smoke_model):
    """EOS mid-stream frees the slot AND its pages; later queue entries
    reuse both; at drain the pool is fully free again."""
    cfg, params, prompts = smoke_model
    eng = ServingEngine(cfg, params,
                        ServeConfig(max_batch=2, max_len=32, kv_format="f32",
                                    kv_layout="paged", page_size=4,
                                    eos_id=0))
    reqs = [Request(uid=i, prompt=prompts[i % len(prompts)], max_new=8)
            for i in range(5)]
    stats = eng.serve(reqs)
    assert all(r.done for r in reqs)
    assert all(len(r.out_tokens) >= 1 for r in reqs)
    assert stats["prefills"] == 5
    assert eng.allocator.live_pages == 0               # no page leaks
    eng.allocator.assert_consistent()
    assert eng.kv_cache_live_bytes() == 0
    assert stats["peak_live_pages"] > 0


def test_engine_no_head_of_line_blocking(smoke_model):
    """An unplaceable queue head must not starve later entries: an
    oversized prompt is rejected outright, and a page-infeasible one
    (paged) is rejected instead of spinning forever."""
    cfg, params, prompts = smoke_model
    eng = ServingEngine(cfg, params,
                        ServeConfig(max_batch=2, max_len=16, kv_format="f32",
                                    kv_layout="paged", page_size=4,
                                    num_pages=5))
    rng = np.random.default_rng(1)
    too_long = Request(uid=0, prompt=rng.integers(0, cfg.vocab, 20),
                       max_new=4)
    # feasible prompts; 12 tokens needs 4 pages = every allocatable page
    big = Request(uid=1, prompt=rng.integers(0, cfg.vocab, 11), max_new=3)
    small = Request(uid=2, prompt=rng.integers(0, cfg.vocab, 3), max_new=3)
    stats = eng.serve([too_long, big, small])
    assert too_long.done and too_long.error is not None
    assert not too_long.out_tokens
    assert stats["rejected"] == 1
    assert len(big.out_tokens) == 3 and len(small.out_tokens) == 3


def test_engine_transient_page_pressure_admits_later_entries(smoke_model):
    """With the pool too tight for the queue head, later small requests
    are admitted first and the head lands once pages free up."""
    cfg, params, prompts = smoke_model
    rng = np.random.default_rng(2)
    eng = ServingEngine(cfg, params,
                        ServeConfig(max_batch=2, max_len=16, kv_format="f32",
                                    kv_layout="paged", page_size=4,
                                    num_pages=6))
    small = Request(uid=0, prompt=rng.integers(0, cfg.vocab, 3), max_new=3)
    # reserves 4 of the 5 allocatable pages: can't start beside a small
    big = Request(uid=1, prompt=rng.integers(0, cfg.vocab, 11), max_new=3)
    small2 = Request(uid=2, prompt=rng.integers(0, cfg.vocab, 3), max_new=3)
    stats = eng.serve([small, big, small2])
    assert stats["rejected"] == 0
    for r in (small, big, small2):
        assert r.done and len(r.out_tokens) == 3


def test_engine_max_new_zero_reserves_first_append_page(smoke_model):
    """Regression: a page-aligned prompt with max_new=0 must still reserve
    the page its first (and only) decode append lands in — otherwise the
    admission invariant undercounts and the request can starve."""
    cfg, params, _ = smoke_model
    rng = np.random.default_rng(3)
    eng = ServingEngine(cfg, params,
                        ServeConfig(max_batch=1, max_len=16, kv_format="f32",
                                    kv_layout="paged", page_size=4,
                                    num_pages=3))
    req = Request(uid=0, prompt=rng.integers(0, cfg.vocab, 4), max_new=0)
    assert eng._worst_pages(req) == 2          # prompt page + append page
    eng.serve([req], max_ticks=50)
    assert req.done and len(req.out_tokens) == 1
    assert eng.allocator.live_pages == 0
    eng.allocator.assert_consistent()


def test_engine_counts_live_and_table_pages_per_tick(smoke_model):
    """Each decode tick adds the live pages of the active slots (their
    length after this tick's append, in pages) to ``engine.kv_pages_live``
    and the whole page table to ``engine.kv_pages_table``."""
    cfg, params, prompts = smoke_model
    ps, max_len = 4, 32
    eng = ServingEngine(cfg, params,
                        ServeConfig(max_batch=3, max_len=max_len,
                                    kv_format="posit8", kv_layout="paged",
                                    page_size=ps))
    reqs = [Request(uid=i, prompt=p, max_new=8)
            for i, p in enumerate(prompts[:2])]
    assert all(eng.add_requests(reqs))
    live = eng.metrics.counter("engine.kv_pages_live")
    table = eng.metrics.counter("engine.kv_pages_table")
    assert live.value == 0 and table.value == 0
    lens = [len(p) for p in prompts[:2]]               # 4 and 11 tokens
    want_live = 0
    for tick in range(3):
        eng.step()
        want_live += sum(pages_for(n + tick + 1, ps) for n in lens)
        assert live.value == want_live
        assert table.value == (tick + 1) * 3 * pages_for(max_len, ps)
    assert eng.stats["kv_pages_live"] == 5 + 6 + 6     # 2+3, 2+4, 2+4


@pytest.mark.parametrize("kvf", ["bf16", "posit8"])
@pytest.mark.parametrize("layout", ["ring", "paged"])
def test_kv_cache_bytes_reports_all_layouts(smoke_model, kvf, layout):
    """Satellite: kv_cache_bytes must be non-zero for every layout (the
    old implementation returned 0 for non-ring key layouts), and the
    paged live accounting stays <= reserved."""
    cfg, params, _ = smoke_model
    eng = ServingEngine(cfg, params,
                        ServeConfig(max_batch=2, max_len=32, kv_format=kvf,
                                    kv_layout=layout, page_size=4))
    reserved = eng.kv_cache_bytes()
    assert reserved > 0
    assert eng.kv_cache_live_bytes() <= reserved
    if layout == "paged":
        assert eng.kv_cache_live_bytes() == 0          # nothing admitted yet
