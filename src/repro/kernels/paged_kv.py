"""Pallas TPU kernels: vLLM-style paged posit KV cache.

The ring cache (``kernels/kv_cache.py``) reserves a dense ``max_len`` ring
per slot, so HBM scales with the worst case.  This module replaces the
per-slot ring with a shared *page pool* plus per-sequence page tables —
the paging indirection the ROADMAP names as the next step after PR 1 —
while keeping the posit code + per-row pow2 scale storage and the
decode-on-read datapath.

Layout (per attention layer; no batch axis — pages are shared):

  pool codes   (R, nkv, Dc)   R = num_pages * page_size flat rows;
                              page p owns rows [p*ps, (p+1)*ps)
  pool scales  (R, nkv) f32   per-(token x head) pow2 scale
  page_table   (B, Pmax) i32  logical page -> physical page per slot;
                              unallocated entries point at page 0, which
                              the allocator reserves as a trash page
  seq_lens     (B,) i32       valid tokens per slot (masks trash reads)

  write path  ``paged_kv_append``     — the destination flat row
      (table[b, pos//ps] * ps + pos%ps) is computed outside and handed to
      the kernel as a scalar-prefetch vector, so only the written
      (nkv, Dc) code rows move from VMEM to HBM and the code buffers are
      donated (``input_output_aliases``), exactly like the ring
      ``kv_append``; the scales go through an XLA scatter.
  read path   ``paged_decode_attention`` — one grid step per live block
      of pages, slot after slot: the page table is scalar-prefetched and
      the *index maps* use it to DMA whole (ps, nkv, Dc) physical pages
      into VMEM, where posit tiles are decoded right before the
      online-softmax MACs.  Pages past a slot's length are never read.
      (m, l, acc) live in VMEM scratch across a slot's blocks.

Pure-jnp references (``paged_kv_append_ref`` / ``paged_decode_attention_ref``
/ ``gather_pages``) share the codec with the kernels, so CPU serving and
the Pallas path agree bit-for-bit on pool contents; the reference read
path reuses ``attention.decode_attention``'s dense masked softmax so ring
and paged greedy decode match exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import PositFormat
from .kv_cache import (decode_kv_rows, encode_kv_rows, encode_scaled_rows,
                       flash_finish, flash_init, flash_rows, flash_scratch,
                       scale_rows)


def flat_dst_rows(page_table, pos, page_size: int):
    """Per-slot flat pool row for writing the token at ``pos``.

    page_table: (B, Pmax) i32; pos: (B,) i32.  The T=1 case of
    ``flat_dst_rows_chunk`` (logical page indices clamped, so idle slots
    whose pos runs past Pmax * ps still map to trash-page rows)."""
    return flat_dst_rows_chunk(page_table, pos, 1, page_size)[:, 0]


def flat_dst_rows_chunk(page_table, pos, t: int, page_size: int):
    """(B, T) flat pool rows for a T-token chunk starting at ``pos``.

    Row [b, i] addresses the token at position pos[b] + i (speculative
    verify writes the whole chunk before scoring it).  Logical page
    indices are clamped exactly like ``flat_dst_rows``, so idle slots
    (all-trash tables) keep writing benign garbage into page 0."""
    pmax = page_table.shape[1]
    pos = (jnp.asarray(pos, jnp.int32)[:, None]
           + jnp.arange(t, dtype=jnp.int32)[None, :])        # (B, T)
    lpi = jnp.clip(pos // page_size, 0, pmax - 1)
    phys = jnp.take_along_axis(page_table, lpi, axis=1)
    return phys * page_size + pos % page_size


# ---------------------------------------------------------------------------
# paged_kv_append: encode-on-write into table-addressed pool rows (Pallas)
# ---------------------------------------------------------------------------

def paged_kv_append(k_codes, k_scale, v_codes, v_scale, k_new, v_new, dst,
                    fmt: PositFormat, *, packed: bool = False,
                    interpret=None):
    """Encode-on-write append into the paged pool.

    k/v_codes: (R, nkv, Dc) pool; k/v_scale: (R, nkv) f32; k/v_new:
    (B, 1, nkv, hd) float; dst: (B,) i32 flat pool rows (``flat_dst_rows``).
    Returns the four updated pool arrays (donated/aliased).  The T=1 case
    of ``paged_kv_append_rows`` — one kernel to maintain, identical codec
    by construction."""
    dst = jnp.asarray(dst, jnp.int32).reshape(k_new.shape[0], 1)
    return paged_kv_append_rows(k_codes, k_scale, v_codes, v_scale, k_new,
                                v_new, dst, fmt, packed=packed,
                                interpret=interpret)


def paged_kv_append_ref(k_codes, k_scale, v_codes, v_scale, k_new, v_new,
                        dst, fmt: PositFormat, packed: bool = False):
    """Pure-jnp oracle for ``paged_kv_append`` (the T=1 case of
    ``paged_kv_append_rows_ref``)."""
    dst = jnp.asarray(dst, jnp.int32).reshape(k_new.shape[0], 1)
    return paged_kv_append_rows_ref(k_codes, k_scale, v_codes, v_scale,
                                    k_new, v_new, dst, fmt, packed)


# ---------------------------------------------------------------------------
# paged_kv_append_rows: chunked encode-on-write into pool rows (Pallas)
# ---------------------------------------------------------------------------

def _paged_append_rows_kernel(dst_ref, kn_ref, vn_ref, kc_ref, vc_ref,
                              kco_ref, vco_ref, *, fmt, packed):
    del dst_ref, kc_ref, vc_ref  # the row address is consumed by the specs
    kco_ref[...] = encode_scaled_rows(kn_ref[...], fmt, packed)
    vco_ref[...] = encode_scaled_rows(vn_ref[...], fmt, packed)


@functools.partial(jax.jit, static_argnames=("fmt", "packed", "interpret"))
def paged_kv_append_rows(k_codes, k_scale, v_codes, v_scale, k_new, v_new,
                         dst, fmt: PositFormat, *, packed: bool = False,
                         interpret=None):
    """Encode-on-write append of a T-token chunk into the paged pool.

    Generalizes ``paged_kv_append`` from one row to T rows per slot:
    k/v_new are (B, T, nkv, hd) floats and ``dst`` is the (B, T) flat-row
    matrix from ``flat_dst_rows_chunk``.  Each block is one whole
    (nkv, Dc) pool row, the codes go through the kernel and the per-row
    scales through an XLA scatter (``kv_cache.scale_rows``).  Live slots
    never share rows; idle slots may collide on the trash page, where the
    last write wins — benign garbage either way."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, t, h, hd = k_new.shape
    dc = k_codes.shape[-1]
    dst = jnp.asarray(dst, jnp.int32).reshape(b, t)
    kx, ks = scale_rows(k_new)
    vx, vs = scale_rows(v_new)

    sq = pl.Squeezed()
    row = pl.BlockSpec((sq, h, dc), lambda i, ti, s: (s[i, ti], 0, 0))
    new = pl.BlockSpec((sq, sq, h, hd), lambda i, ti, s: (i, ti, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    kc, vc = pl.pallas_call(
        functools.partial(_paged_append_rows_kernel, fmt=fmt, packed=packed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, t),
            in_specs=[new, new, hbm, hbm], out_specs=[row, row]),
        out_shape=[jax.ShapeDtypeStruct(k_codes.shape, k_codes.dtype),
                   jax.ShapeDtypeStruct(v_codes.shape, v_codes.dtype)],
        # operand indices include the scalar-prefetch arg (index 0)
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
    )(dst, kx, vx, k_codes, v_codes)
    flat = dst.reshape(b * t)
    return (kc, k_scale.at[flat].set(ks.reshape(b * t, h)), vc,
            v_scale.at[flat].set(vs.reshape(b * t, h)))


def paged_kv_append_rows_ref(k_codes, k_scale, v_codes, v_scale, k_new,
                             v_new, dst, fmt: PositFormat,
                             packed: bool = False):
    """Pure-jnp oracle for ``paged_kv_append_rows`` (same codec, scatter)."""
    b, t = k_new.shape[:2]
    dst = jnp.asarray(dst, jnp.int32).reshape(b * t)

    def wr(codes, scale, new):
        c, s = encode_kv_rows(new, fmt, packed)          # (B, T, nkv, Dc)
        codes = codes.at[dst].set(
            c.reshape((b * t,) + c.shape[2:]).astype(codes.dtype))
        scale = scale.at[dst].set(s[..., 0].reshape(b * t, -1))
        return codes, scale

    kc, ks = wr(k_codes, k_scale, k_new)
    vc, vs = wr(v_codes, v_scale, v_new)
    return kc, ks, vc, vs


# ---------------------------------------------------------------------------
# paged_decode_attention: page-walking fused decode (Pallas)
# ---------------------------------------------------------------------------

BLOCK_ROWS = 128   # pool rows a grid step of the page walk covers


def pages_per_block(page_size: int, pmax: int) -> int:
    """Pages one grid step of ``paged_decode_attention`` covers: about
    ``BLOCK_ROWS`` rows, never more pages than the table holds."""
    return max(1, min(BLOCK_ROWS // page_size, pmax))


def _paged_attn_kernel(tbl_ref, len_ref, slot_ref, blk_ref, q_ref, *refs,
                       fmt, packed, ps, ppb):
    del tbl_ref  # consumed by the index maps (page DMA addressing)
    kc, ks, vc, vs = (refs[a * ppb:(a + 1) * ppb] for a in range(4))
    o_ref, m_ref, l_ref, acc_ref = refs[4 * ppb:]
    step = pl.program_id(0)
    blk = blk_ref[step]
    n_valid = len_ref[slot_ref[step]] - blk * ppb * ps

    @pl.when(blk == 0)
    def _init():
        flash_init(m_ref, l_ref, acc_ref)

    @pl.when(n_valid > 0)
    def _live():
        flash_rows(q_ref, kc, ks, vc, vs, m_ref, l_ref, acc_ref, n_valid,
                   fmt=fmt, packed=packed)

    @pl.when(n_valid <= ppb * ps)           # the slot's last block
    def _finish():
        flash_finish(o_ref, l_ref, acc_ref)


def _page_index(j, ps, ppb):
    """Index map of a block's ``j``-th page: the physical page of logical
    page ``blk * ppb + j`` of the step's slot.  Past the slot's last live
    page it names the page the same operand held in the block before, so
    the pipeline issues no DMA for it; in a slot's first block, which has
    no block before, it names the last live page."""
    def index(step, t, ln, slot, blk):
        i, blk = slot[step], blk[step]
        last = jnp.maximum(ln[i] - 1, 0) // ps          # last live page
        page = blk * ppb + j
        page = jnp.where(page <= last, page,
                         jnp.where(blk > 0, page - ppb, last))
        return t[i, page]
    return index


@functools.partial(jax.jit, static_argnames=("fmt", "page_size", "packed",
                                             "interpret"))
def paged_decode_attention(q, k_codes, k_scale, v_codes, v_scale,
                           page_table, seq_lens, fmt: PositFormat, *,
                           page_size: int, packed: bool = False,
                           interpret=None):
    """Fused one-token GQA attention over a paged posit pool.

    q: (B, 1, nh, hd); k/v_codes: (R, nkv, Dc) pool; k/v_scale:
    (R, nkv); page_table: (B, Pmax) i32 (entries must be valid physical
    pages — unallocated logical pages point at the trash page);
    seq_lens: (B,) i32.  The grid has one step per live block of
    ``pages_per_block`` pages, slot after slot (its size is worked out
    from ``seq_lens`` on the device), so the cost follows each slot's
    live KV, not Pmax.  Each page of a block is its own operand, whose
    index map reads the scalar-prefetched table, so the pipeline DMAs
    whole (page_size, nkv, Dc) pages of all KV heads while the step
    before computes; a page past the slot's length repeats what its
    operand already holds, so no DMA is issued for it.  A block runs one
    online-softmax update over all its rows, (m, l, acc) carried in VMEM
    scratch.  On the TPU ``page_size`` must divide by 8.  Returns
    (B, 1, nh, hd)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    r, nkv, dc = k_codes.shape
    b, _, nh, hd = q.shape
    grp = nh // nkv
    npg = page_table.shape[1]
    num_pages = r // page_size
    ps = page_size
    ppb = pages_per_block(ps, npg)
    bk = ppb * ps
    tbl = jnp.clip(jnp.asarray(page_table, jnp.int32), 0, num_pages - 1)
    lens = jnp.minimum(
        jnp.broadcast_to(jnp.asarray(seq_lens, jnp.int32), (b,)), npg * ps)
    qg = (q.reshape(b, nkv, grp, hd) * (hd ** -0.5)).astype(jnp.float32)
    # one grid step per live block, slot after slot; a slot with no live
    # row still takes one step, which writes its (zero) output.  The
    # pipeline reads the step arrays past the grid's last step: on a v5e
    # with every slot's table full, one or two spare entries halted the
    # core and eight did not.  The spares repeat the last block
    nblk = jnp.maximum(-(-lens // bk), 1)
    first = jnp.cumsum(nblk) - nblk
    steps = jnp.arange(b * -(-npg // ppb) + 8, dtype=jnp.int32)
    slot = jnp.sum(first[None, :] <= steps[:, None], axis=1,
                   dtype=jnp.int32) - 1
    blk = jnp.minimum(steps - first[slot], nblk[slot] - 1)

    sq = pl.Squeezed()
    heads = pl.BlockSpec((sq, nkv, grp, hd),
                         lambda s, t, ln, sl, bl: (sl[s], 0, 0, 0))
    codes = [pl.BlockSpec((ps, nkv, dc), lambda *a, f=_page_index(
        j, ps, ppb): (f(*a), 0, 0)) for j in range(ppb)]
    scales = [pl.BlockSpec((ps, nkv), lambda *a, f=_page_index(
        j, ps, ppb): (f(*a), 0)) for j in range(ppb)]
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, fmt=fmt, packed=packed,
                          ps=ps, ppb=ppb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(jnp.sum(nblk),),
            in_specs=[heads] + codes + scales + codes + scales,
            out_specs=heads, scratch_shapes=flash_scratch(nkv, grp, hd)),
        out_shape=jax.ShapeDtypeStruct((b, nkv, grp, hd), jnp.float32),
        interpret=interpret,
    )(tbl, lens, slot, blk, qg, *[k_codes] * ppb, *[k_scale] * ppb,
      *[v_codes] * ppb, *[v_scale] * ppb)
    return out.reshape(b, 1, nh, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pure-jnp references
# ---------------------------------------------------------------------------

def gather_pages(pool, page_table, page_size: int):
    """Gather a per-slot logical view from a flat pool.

    pool: (R, ...) flat rows; page_table: (B, Pmax).  Returns
    (B, Pmax * page_size, ...) — logical token order, trash rows included
    (callers mask by seq_lens)."""
    num_pages = pool.shape[0] // page_size
    tbl = jnp.clip(jnp.asarray(page_table, jnp.int32), 0, num_pages - 1)
    rows = tbl[:, :, None] * page_size + jnp.arange(page_size)[None, None, :]
    b, npg = tbl.shape
    return pool[rows.reshape(b, npg * page_size)]


def gather_decode_pages(codes, scales, page_table, page_size: int,
                        fmt: PositFormat, packed: bool = False):
    """Gather a slot-logical view of a posit pool and decode it to floats:
    (R, nkv, Dc) codes + (R, nkv) scales -> (B, Pmax*ps, nkv, hd).  The
    single codec path shared by the reference attention and the serving
    fallbacks, so ring/paged equivalence has one implementation to pin."""
    return decode_kv_rows(
        gather_pages(codes, page_table, page_size),
        gather_pages(scales, page_table, page_size)[..., None], fmt, packed)


def paged_decode_attention_ref(q, k_codes, k_scale, v_codes, v_scale,
                               page_table, seq_lens, fmt: PositFormat, *,
                               page_size: int, packed: bool = False):
    """Pure-jnp oracle: gather the page list, decode, dense masked softmax
    (via ``attention.decode_attention`` so ring/paged refs share the exact
    reduction order)."""
    from ..models.attention import decode_attention
    k = gather_decode_pages(k_codes, k_scale, page_table, page_size, fmt,
                            packed)
    v = gather_decode_pages(v_codes, v_scale, page_table, page_size, fmt,
                            packed)
    return decode_attention(q, k, v, seq_lens)
