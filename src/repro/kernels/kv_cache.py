"""Pallas TPU kernels: posit-packed KV cache for serving decode.

The KV cache is the dominant HBM consumer during batched decode.  This
module stores the attention K/V rings as posit codes with a per-row
(token x head) power-of-two scale and keeps them packed end to end:

  write path  ``kv_append``       — one token's K/V rows are scaled,
      RNE-encoded and stored straight into the ring at ``pos % W``.  The
      ring position is a scalar-prefetch operand, so only the written
      (H, Dc) code rows move from VMEM to HBM (no full-ring
      read-modify-write), and the code buffers are donated via
      ``input_output_aliases``; the per-row scales go through an XLA
      scatter.
  read path   ``decode_attention`` — fused decode-on-read flash decode:
      posit K/V tiles are decoded to f32 *in VMEM* right before the
      online-softmax inner loop (grid innermost over KV blocks, (m, l,
      acc) carried in VMEM scratch), mirroring the decode-in-VMEM
      structure of ``posit_matmul``.  Full-precision K/V never
      round-trips through HBM: HBM carries ``bits/16`` of the bf16
      baseline (plus one f32 scale per hd-row).

Sub-byte storage: P(4, 1) codes are nibble-packed two-per-byte along the
head dim (split-half layout: byte j holds elements j and j + hd/2, so
unpacking is a lane concatenation, not a gather).  With hd = 64 the cache
lands at ~0.28x the bf16 footprint; posit8 at ~0.53x.

Pure-jnp references (``encode_kv_rows`` / ``decode_kv_rows`` /
``decode_attention_ref``) share the scale rule and codec with the kernel
bodies, so the CPU serving path and the Pallas path are bit-identical on
the cache contents.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import PositFormat
from .posit_decode import decode_tile
from .posit_encode import encode_tile

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Shared codec helpers (pure jnp, Pallas-safe: used in kernel bodies and refs)
# ---------------------------------------------------------------------------

def row_pow2_scale(x):
    """Per-row power-of-two scale over the last axis: 2**floor(log2(mean|x|)).

    Exact (exponent-bit extraction, no transcendentals) so applying and
    removing the scale is lossless and the kernel/reference paths agree
    bit-for-bit.  Returns shape ``x.shape[:-1] + (1,)`` float32, >= 2^-98.
    """
    absx = jnp.abs(x.astype(jnp.float32))
    mean = jnp.maximum(jnp.mean(absx, axis=-1, keepdims=True), 1e-30)
    e = (jax.lax.bitcast_convert_type(mean, jnp.int32) >> 23) & 0xFF
    return jax.lax.bitcast_convert_type(e << 23, jnp.float32)


def pack_nibbles(codes):
    """(..., D) 4-bit codes (uint8, < 16) -> (..., D//2) split-half packed:
    byte j = codes[j] | codes[j + D/2] << 4."""
    d = codes.shape[-1]
    lo, hi = codes[..., : d // 2], codes[..., d // 2:]
    return lo | (hi << 4)


def unpack_nibbles(packed):
    """(..., D//2) packed bytes -> (..., D) 4-bit codes (lane concat)."""
    return jnp.concatenate([packed & 0xF, packed >> 4], axis=-1)


def scale_rows(x):
    """Float rows (..., hd) -> (rows / scale, scale (...) f32): the XLA half
    of an append.  The kernels store scales through an XLA scatter because
    a (1, nkv) f32 row of a lane-padded (.., nkv) array is neither a legal
    Mosaic block nor a legal DMA window."""
    x = x.astype(jnp.float32)
    scale = row_pow2_scale(x)
    return x / scale, scale[..., 0]


def encode_scaled_rows(x, fmt: PositFormat, packed: bool):
    """Rows already divided by their pow2 scale -> codes (the kernel-body
    half of an append).  Nibble packing happens on int32 lanes and the
    result is narrowed to the storage dtype last, as Mosaic asks."""
    if not packed:
        return encode_tile(x, fmt)
    codes = encode_tile(x, fmt).astype(jnp.int32)
    return pack_nibbles(codes).astype(fmt.storage_dtype)


def encode_kv_rows(x, fmt: PositFormat, packed: bool = False):
    """Float rows (..., hd) -> (codes, scale (..., 1) f32).

    Per-row pow2 scale centres the posit tapered-precision region on the
    row's magnitude; codes are bit-exact RNE posit.  ``packed`` nibble-packs
    4-bit codes (hd must be even)."""
    xs, scale = scale_rows(x)
    return encode_scaled_rows(xs, fmt, packed), scale[..., None]


def decode_kv_rows(codes, scale, fmt: PositFormat, packed: bool = False,
                   out_dtype=jnp.float32):
    """Inverse of ``encode_kv_rows``; scale broadcastable over the rows."""
    if packed:
        codes = unpack_nibbles(codes)
    v = decode_tile(codes, fmt, jnp.float32)
    return (v * scale).astype(out_dtype)


def code_channels(hd: int, fmt: PositFormat, packed: bool = False) -> int:
    """Last-axis size of the code buffer for hd float channels."""
    if packed:
        assert hd % 2 == 0, "nibble packing needs an even head dim"
        return hd // 2
    return hd


# ---------------------------------------------------------------------------
# kv_append: encode-on-write ring update (Pallas)
# ---------------------------------------------------------------------------

def kv_append(k_codes, k_scale, v_codes, v_scale, k_new, v_new, pos,
              fmt: PositFormat, *, packed: bool = False, interpret=None):
    """Encode-on-write ring append.

    k/v_codes: (B, W, H, Dc) posit codes; k/v_scale: (B, W, H) f32;
    k/v_new: (B, 1, H, hd) float; pos: int position, scalar (shared) or
    (B,) per-slot (mod W applied here).  Returns the four updated cache
    arrays (donated/aliased).  The T=1 case of ``kv_append_rows`` — one
    kernel to maintain, identical codec by construction."""
    return kv_append_rows(k_codes, k_scale, v_codes, v_scale, k_new, v_new,
                          pos, fmt, packed=packed, interpret=interpret)


def kv_append_ref(k_codes, k_scale, v_codes, v_scale, k_new, v_new, pos,
                  fmt: PositFormat, packed: bool = False):
    """Pure-jnp oracle for ``kv_append`` (the T=1 case of
    ``kv_append_rows_ref``).  ``pos`` scalar (shared) or (B,) per-slot."""
    return kv_append_rows_ref(k_codes, k_scale, v_codes, v_scale, k_new,
                              v_new, pos, fmt, packed)


# ---------------------------------------------------------------------------
# kv_append_rows: encode-on-write ring update for a T-token chunk (Pallas)
# ---------------------------------------------------------------------------

def _append_rows_kernel(idx_ref, kn_ref, vn_ref, kc_ref, vc_ref, kco_ref,
                        vco_ref, *, fmt, packed):
    del idx_ref, kc_ref, vc_ref  # the row address is consumed by the specs
    kco_ref[...] = encode_scaled_rows(kn_ref[...], fmt, packed)
    vco_ref[...] = encode_scaled_rows(vn_ref[...], fmt, packed)


@functools.partial(jax.jit, static_argnames=("fmt", "packed", "interpret"))
def kv_append_rows(k_codes, k_scale, v_codes, v_scale, k_new, v_new, pos,
                   fmt: PositFormat, *, packed: bool = False, interpret=None):
    """Encode-on-write ring append of a T-token chunk (speculative verify).

    Generalizes ``kv_append`` from one row to T consecutive rows per slot:
    k/v_new are (B, T, H, hd) floats and ``pos`` is the (B,) per-slot start
    position — token t of slot b lands at ring index (pos[b] + t) mod W.
    The (B, T) index matrix is a scalar-prefetch operand, so only the
    written (H, Dc) code rows move from VMEM to HBM and the code buffers
    are donated.  Each block spans all heads of a row, so its trailing
    (H, Dc) dims are whole, as the TPU tiling rule asks."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, w, h, dc = k_codes.shape
    t, hd = k_new.shape[1], k_new.shape[-1]
    idx = (jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))[:, None]
           + jnp.arange(t, dtype=jnp.int32)[None, :]) % w
    kx, ks = scale_rows(k_new)
    vx, vs = scale_rows(v_new)

    sq = pl.Squeezed()
    row = pl.BlockSpec((sq, sq, h, dc), lambda i, ti, s: (i, s[i, ti], 0, 0))
    new = pl.BlockSpec((sq, sq, h, hd), lambda i, ti, s: (i, ti, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    kc, vc = pl.pallas_call(
        functools.partial(_append_rows_kernel, fmt=fmt, packed=packed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, t),
            in_specs=[new, new, hbm, hbm], out_specs=[row, row]),
        out_shape=[jax.ShapeDtypeStruct(k_codes.shape, k_codes.dtype),
                   jax.ShapeDtypeStruct(v_codes.shape, v_codes.dtype)],
        # operand indices include the scalar-prefetch arg (index 0)
        input_output_aliases={3: 0, 4: 1},
        interpret=interpret,
    )(idx, kx, vx, k_codes, v_codes)
    rows = jnp.arange(b)[:, None]
    return (kc, k_scale.at[rows, idx].set(ks), vc,
            v_scale.at[rows, idx].set(vs))


def kv_append_rows_ref(k_codes, k_scale, v_codes, v_scale, k_new, v_new, pos,
                       fmt: PositFormat, packed: bool = False):
    """Pure-jnp oracle for ``kv_append_rows`` (same codec, XLA scatter)."""
    b, w = k_codes.shape[:2]
    t = k_new.shape[1]
    idx = (jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))[:, None]
           + jnp.arange(t, dtype=jnp.int32)[None, :]) % w
    rows = jnp.arange(b)[:, None]

    def wr(codes, scale, new):
        c, s = encode_kv_rows(new, fmt, packed)         # (B, T, H, Dc)
        codes = codes.at[rows, idx].set(c.astype(codes.dtype))
        scale = scale.at[rows, idx].set(s[..., 0])
        return codes, scale

    kc, ks = wr(k_codes, k_scale, k_new)
    vc, vs = wr(v_codes, v_scale, v_new)
    return kc, ks, vc, vs


# ---------------------------------------------------------------------------
# decode_attention: fused decode-on-read flash decode (Pallas)
# ---------------------------------------------------------------------------

def flash_block(q_ref, kc_ref, ks_ref, vc_ref, vs_ref, m_ref, l_ref,
                 acc_ref, first, n_valid, *, fmt, packed):
    """One KV block of the online softmax, for every KV head of a row.

    kc/vc refs: (rows, nkv, Dc) codes; ks/vs refs: (rows, nkv) scales;
    q_ref: (nkv, grp, hd).  Keys at block offsets >= ``n_valid`` are
    masked (their V rows are zeroed too, so stale or padded codes cannot
    leak a NaN into the sum).  Decode-on-read: each head's posit codes
    become f32 in VMEM right before the MXU consumes them."""
    @pl.when(first)
    def _init():
        flash_init(m_ref, l_ref, acc_ref)

    flash_rows(q_ref, [kc_ref], [ks_ref], [vc_ref], [vs_ref], m_ref, l_ref,
               acc_ref, n_valid, fmt=fmt, packed=packed)


def flash_rows(q_ref, kc_refs, ks_refs, vc_refs, vs_refs, m_ref, l_ref,
               acc_ref, n_valid, *, fmt, packed):
    """``flash_block``'s update over a block whose rows are those of the
    listed refs, in order (the paged kernel's pages of one block): each
    head's codes and scales are joined along the rows, then decoded and
    reduced as one tile."""
    nkv = q_ref.shape[0]
    rows = sum(r.shape[0] for r in kc_refs)

    def join(parts):
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)

    col_ok = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1) < n_valid
    row_ok = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) < n_valid
    for j in range(nkv):
        kc = join([r[:, j, :].astype(jnp.int32) for r in kc_refs])  # (rows, Dc)
        vc = join([r[:, j, :].astype(jnp.int32) for r in vc_refs])
        if packed:
            kc, vc = unpack_nibbles(kc), unpack_nibbles(vc)
        k = decode_tile(kc, fmt) * join([r[:, pl.ds(j, 1)] for r in ks_refs])
        v = decode_tile(vc, fmt) * join([r[:, pl.ds(j, 1)] for r in vs_refs])
        v = jnp.where(row_ok, v, 0.0)                               # (rows, hd)
        q = q_ref[j].astype(jnp.float32)                            # (grp, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(col_ok, s, NEG_INF)                           # (grp, rows)
        m_prev = m_ref[j]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[j] = l_ref[j] * corr + p.sum(-1, keepdims=True)
        acc_ref[j] = acc_ref[j] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[j] = m_new


def flash_init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def flash_finish(o_ref, l_ref, acc_ref):
    o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def flash_scratch(nkv: int, grp: int, hd: int):
    return [pltpu.VMEM((nkv, grp, 1), jnp.float32),
            pltpu.VMEM((nkv, grp, 1), jnp.float32),
            pltpu.VMEM((nkv, grp, hd), jnp.float32)]


def _decode_attn_kernel(len_ref, q_ref, kc_ref, ks_ref, vc_ref, vs_ref,
                        o_ref, m_ref, l_ref, acc_ref, *, fmt, packed, bw, nw):
    bi = pl.program_id(0)
    wi = pl.program_id(1)
    flash_block(q_ref, kc_ref, ks_ref, vc_ref, vs_ref, m_ref, l_ref,
                 acc_ref, wi == 0,
                 len_ref[bi] - wi * bw, fmt=fmt, packed=packed)

    @pl.when(wi == nw - 1)
    def _finish():
        flash_finish(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("fmt", "packed", "block_w",
                                             "interpret"))
def decode_attention(q, k_codes, k_scale, v_codes, v_scale, cache_len,
                     fmt: PositFormat, *, packed: bool = False,
                     block_w: int = 128, interpret=None):
    """Fused one-token GQA attention over a posit-packed ring.

    q: (B, 1, nh, hd); k/v_codes: (B, W, nkv, Dc); k/v_scale: (B, W, nkv);
    cache_len: count of valid ring entries, scalar (shared) or (B,)
    per-slot.  The grid walks (slot, KV block); each step reads one
    (block_w, nkv, Dc) block of the ring in place, all KV heads at once,
    and runs the online softmax with decode-in-VMEM.  On the TPU
    ``block_w`` must divide by 8 (or equal W).  Returns (B, 1, nh, hd)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, w, nkv, dc = k_codes.shape
    nh, hd = q.shape[2], q.shape[3]
    grp = nh // nkv
    bw = min(block_w, w)
    nw = -(-w // bw)
    qg = (q.reshape(b, nkv, grp, hd) * (hd ** -0.5)).astype(jnp.float32)
    lens = jnp.minimum(
        jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,)), w)
    sq = pl.Squeezed()
    codes = pl.BlockSpec((sq, bw, nkv, dc), lambda i, wi, ln: (i, wi, 0, 0))
    scales = pl.BlockSpec((sq, bw, nkv), lambda i, wi, ln: (i, wi, 0))
    heads = pl.BlockSpec((sq, nkv, grp, hd), lambda i, wi, ln: (i, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_decode_attn_kernel, fmt=fmt, packed=packed,
                          bw=bw, nw=nw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, nw),
            in_specs=[heads, codes, scales, codes, scales],
            out_specs=heads, scratch_shapes=flash_scratch(nkv, grp, hd)),
        out_shape=jax.ShapeDtypeStruct((b, nkv, grp, hd), jnp.float32),
        interpret=interpret,
    )(lens, qg, k_codes, k_scale, v_codes, v_scale)
    return out.reshape(b, 1, nh, hd).astype(q.dtype)


def decode_attention_ref(q, k_codes, k_scale, v_codes, v_scale, cache_len,
                         fmt: PositFormat, packed: bool = False):
    """Pure-jnp oracle: decode the whole ring, dense masked softmax.
    ``cache_len`` scalar (shared) or (B,) per-slot."""
    b, w, nkv, _ = k_codes.shape
    nh, hd = q.shape[2], q.shape[3]
    grp = nh // nkv
    k = decode_kv_rows(k_codes, k_scale[..., None], fmt, packed)
    v = decode_kv_rows(v_codes, v_scale[..., None], fmt, packed)
    qg = q.reshape(b, 1, nkv, grp, hd).astype(jnp.float32) * (hd ** -0.5)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qg, k)
    cl = jnp.broadcast_to(jnp.asarray(cache_len), (b,))
    s = jnp.where((jnp.arange(w)[None, :] < cl[:, None])
                  [:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, v)
    return out.reshape(b, 1, nh, hd).astype(q.dtype)
