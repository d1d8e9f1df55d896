"""Pallas TPU kernel: float -> posit encode (quantize-on-store).

Bit-exact RNE assembly (guard/sticky on the regime/exponent/fraction
concatenation), saturating to maxpos/minpos.  Used for KV-cache / gradient
wire quantization where the store side is the bandwidth bottleneck.

float32 subnormal inputs (|x| < 2^-126) are flushed to zero inside the
kernel: every assigned posit format maps them to minpos/zero anyway and this
keeps the body free of clz (VPU compare/shift/add only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.formats import PositFormat
from .posit_decode import I32, check_kernel_format, mask_i32


def encode_tile(x, fmt: PositFormat):
    """Encode a float32 tile to posit codes. Pallas-safe (signed int32 only,
    narrowed to the storage dtype at the end); bit-exact RNE for normal
    floats (subnormals flushed — see module docstring)."""
    check_kernel_format(fmt)
    n, es = fmt.bits, fmt.es
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), I32)
    s = (bits >> 31) & 1
    exp_raw = (bits >> 23) & 0xFF
    frac = bits & mask_i32(23)
    is_zero = ((bits & 0x7FFFFFFF) == 0) | (exp_raw == 0)  # flush subnormals
    is_nar = exp_raw == 255
    t = exp_raw - 127 - fmt.bias
    fw = 23
    # --- regime/exponent split ---
    k = t >> es
    e_field = t - (k << es)
    sat_hi = k >= n - 2
    sat_lo = k <= -(n - 1)
    k_c = jnp.clip(k, -(n - 2), n - 3)
    pos = k_c >= 0
    w0 = jnp.where(pos, k_c + 2, 1 - k_c)
    reg = jnp.where(pos, mask_i32(k_c + 1) << 1, 1)
    avail = (n - 1) - w0
    ef_shift = avail + 1 - es
    # --- case ef_shift >= 0 ---
    efp = jnp.maximum(ef_shift, 0)
    take = jnp.minimum(efp, fw)
    fbits = (frac >> (fw - take)) << (efp - take)
    st_a = ((frac & mask_i32(fw - take)) != 0).astype(I32)
    efg_a = (e_field << efp) | fbits
    # --- case ef_shift < 0 ---
    cut = jnp.maximum(-ef_shift, 0)
    efg_b = e_field >> cut
    st_b = (((e_field & mask_i32(cut)) != 0) | (frac != 0)).astype(I32)
    neg_case = ef_shift < 0
    efg = jnp.where(neg_case, efg_b, efg_a)
    st = jnp.where(neg_case, st_b, st_a)
    guard = efg & 1
    kept = efg >> 1
    body = (reg << avail) | kept
    body = body + (guard & (st | (body & 1)))
    body = jnp.where(sat_hi, mask_i32(n - 1), body)
    body = jnp.where(sat_lo, 1, body)
    body = jnp.clip(body, 1, mask_i32(n - 1))
    code = jnp.where(s == 1, (-body) & mask_i32(n), body)
    code = jnp.where(is_zero, 0, code)
    code = jnp.where(is_nar, 1 << (n - 1), code)
    return code.astype(fmt.storage_dtype)


def _encode_kernel(x_ref, o_ref, *, fmt):
    o_ref[...] = encode_tile(x_ref[...], fmt)


@functools.partial(jax.jit, static_argnames=("fmt", "block", "interpret"))
def posit_encode(x, fmt: PositFormat, *, block=(256, 256), interpret=None):
    """Blocked posit encode. x: (M, N) float -> (M, N) posit codes."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, n = x.shape
    bm, bn = min(block[0], m), min(block[1], n)
    pm, pn = -m % bm, -n % bn
    padded = jnp.pad(x, ((0, pm), (0, pn)))
    out = pl.pallas_call(
        functools.partial(_encode_kernel, fmt=fmt),
        grid=(padded.shape[0] // bm, padded.shape[1] // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(padded.shape, fmt.storage_dtype),
        interpret=interpret,
    )(padded)
    return out[:m, :n]
