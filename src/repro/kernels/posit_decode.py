"""Pallas TPU kernel: posit -> float decode (Algorithm 1 on the VPU).

The kernel body is the paper's decode, vectorized: the regime is found with
n-1 *parallel threshold comparisons* (the thermometer/Q-function form —
deliberately not clz, so every op is a plain VPU compare/add and the kernel
mirrors the TALU datapath), then exponent/fraction are exposed with shifts
and the IEEE-754 bit pattern is assembled integer-only (no transcendentals).

Tiles are (block_m, block_n) in VMEM; codes are uint8/uint16, output f32 or
bf16.  Arithmetic intensity is trivial (this kernel exists to *fuse* into
consumers — see posit_matmul which inlines `decode_tile`), but a standalone
decode is useful for cache/state dequantization.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.formats import PositFormat

# Mosaic has no unsigned min/max/shift, so the codec keeps every field in
# signed int32 (a posit of <= 16 bits never needs the sign bit).
I32 = jnp.int32


def mask_i32(b):
    """(1 << b) - 1 as int32 for 0 <= b <= 30; ``b`` may be traced."""
    return (jnp.int32(1) << b) - 1


def check_kernel_format(fmt: PositFormat):
    if fmt.bits > 16:
        raise ValueError(f"kernel codec handles posits up to 16 bits, got "
                         f"{fmt.name} ({fmt.bits} bits)")


def decode_tile(codes, fmt: PositFormat, out_dtype=jnp.float32):
    """Decode a tile of posit codes to float. Pure jnp; Pallas-safe ops only
    (signed int32 compares, shifts, adds — no clz, no gather, no unsigned
    arithmetic, which Mosaic cannot lower). Bit-exact for n<=16."""
    check_kernel_format(fmt)
    n, es = fmt.bits, fmt.es
    u = codes.astype(I32) & mask_i32(n)
    is_zero = u == 0
    is_nar = u == (1 << (n - 1))
    s = (u >> (n - 1)) & 1
    mag = jnp.where(s == 1, (-u) & mask_i32(n), u)
    body = mag & mask_i32(n - 1)
    lead = (body >> (n - 2)) & 1
    t_val = jnp.where(lead == 1, body, (~body) & mask_i32(n - 1))
    # --- Algorithm 1: parallel threshold comparisons (unrolled, VPU) ---
    r = jnp.zeros_like(u)
    for i in range(n - 1):
        thr = (1 << (n - 1)) - (1 << i)  # 2^{n-1}-1-(2^i-1)
        r = r + (t_val >= thr).astype(I32)
    k = jnp.where(lead == 1, r - 1, -r)
    rem = jnp.maximum(n - 2 - r, 0)
    rest = body & mask_i32(rem)
    e_have = jnp.minimum(rem, es)
    e_field = (rest >> (rem - e_have)) << (es - e_have)
    f_len = jnp.maximum(rem - es, 0)
    f_field = rest & mask_i32(f_len)
    t = (k << es) + e_field + fmt.bias
    # --- IEEE-754 assembly (f_len <= 13 <= 23: exact) ---
    man = f_field << (23 - f_len)
    bits = (s << 31) | ((t + 127) << 23) | man
    val = jax.lax.bitcast_convert_type(bits, jnp.float32)
    val = jnp.where(is_zero, 0.0, val)
    val = jnp.where(is_nar, jnp.nan, val)
    return val.astype(out_dtype)


def _decode_kernel(c_ref, o_ref, *, fmt, out_dtype):
    o_ref[...] = decode_tile(c_ref[...], fmt, out_dtype)


@functools.partial(jax.jit, static_argnames=("fmt", "block", "out_dtype", "interpret"))
def posit_decode(codes, fmt: PositFormat, *, block=(256, 256),
                 out_dtype=jnp.float32, interpret=None):
    """Blocked posit decode. codes: (M, N) uint8/uint16 -> (M, N) float."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    m, n = codes.shape
    bm, bn = min(block[0], m), min(block[1], n)
    pm, pn = -m % bm, -n % bn
    padded = jnp.pad(codes, ((0, pm), (0, pn)))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, fmt=fmt, out_dtype=out_dtype),
        grid=(padded.shape[0] // bm, padded.shape[1] // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(padded.shape, out_dtype),
        interpret=interpret,
    )(padded)
    return out[:m, :n]
