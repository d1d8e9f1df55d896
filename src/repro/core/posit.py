"""Vectorized posit codec + arithmetic in pure JAX.

This is the paper's primary algorithmic contribution mapped to the TPU VPU:

* ``thermometer_decode`` implements Algorithm 1 verbatim: n-1 *parallel
  threshold comparisons* ``V_i = T >= 2^{n-1} - 2^i`` produce a thermometer
  code whose popcount is the regime run-length; a LUT (here: popcount — we
  prove the equivalence in tests) yields the regime value K, and one left
  shift exposes exponent and fraction.  Branch-free and fixed-depth, exactly
  as on the TALU clusters.
* ``decode_to_f32`` / ``encode_f32`` convert between posit codes and float32
  with bit-exact softposit semantics (see ``posit_ref``): two's-complement
  negatives, right-zero-filled truncated exponents, bit-level RNE,
  maxpos/minpos saturation.
* ``add`` / ``mul`` / ``fma`` are *exact* posit arithmetic for n<=16 (int32
  internals) — the software analogue of TALU's compute mode, used by the
  edge-emulation path and the accuracy benchmarks.

All functions are shape-polymorphic and jit/vmap/shard_map-friendly; bit
manipulation uses uint32 (logical shifts) and int32 (signed exponents) only,
so nothing here requires x64 mode.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .formats import PositFormat

U32 = jnp.uint32
I32 = jnp.int32


def _u(x):
    return jnp.asarray(x).astype(U32)


def _i(x):
    return jnp.asarray(x).astype(I32)


def _mask(b):
    """(1<<b)-1 as uint32, valid for b in [0,32], b may be a traced array."""
    b = jnp.asarray(b, U32)
    full = jnp.asarray(0xFFFFFFFF, U32)
    return jnp.where(b >= 32, full, (U32(1) << jnp.minimum(b, U32(31))) - U32(1))


def _shl(x, k):
    """uint32 left shift, clamped: k>=32 -> 0; k is non-negative."""
    k = jnp.asarray(k, U32)
    return jnp.where(k >= 32, U32(0), _u(x) << jnp.minimum(k, U32(31)))


def _shr(x, k):
    """uint32 logical right shift, clamped: k>=32 -> 0."""
    k = jnp.asarray(k, U32)
    return jnp.where(k >= 32, U32(0), _u(x) >> jnp.minimum(k, U32(31)))


def _negate_code(u, n):
    """Two's-complement negation within n bits (uint32)."""
    return (~u + U32(1)) & _mask(n)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def thermometer_decode(codes, fmt: PositFormat):
    """Algorithm 1's Find_R, verbatim: parallel threshold comparisons.

    Returns (V, r, K) where V is the (..., n-1) thermometer matrix of
    Q-function outputs ``V_i = T[n-2:0] >= 2^{n-1}-1-(2^i-1)``, r = popcount(V)
    is the regime run length and K the regime value.  Operates on the raw
    code the way the TALU does (magnitude handling happens upstream).
    """
    n = fmt.bits
    u = _u(jnp.asarray(codes))
    body = u & _mask(n - 1)
    lead = _shr(body, n - 2) & U32(1)
    t_val = jnp.where(lead == 1, body, (~body) & _mask(n - 1))
    i = jnp.arange(n - 1, dtype=np.int64)
    thresholds = ((1 << (n - 1)) - 1 - ((1 << i) - 1)).astype(np.uint32)  # 2^{n-1}-2^i
    v = (t_val[..., None] >= thresholds).astype(U32)
    r = jnp.sum(v, axis=-1, dtype=U32)
    k = jnp.where(lead == 1, _i(r) - 1, -_i(r))
    return v, r, k


def regime_lut(fmt: PositFormat) -> np.ndarray:
    """The paper's LUT: thermometer popcount -> K (for lead=1 plane).

    Built by enumeration, used in tests to prove LUT[V] == popcount-derived K.
    """
    n = fmt.bits
    return np.arange(n, dtype=np.int32) - 1


def _decode_parts(codes, fmt: PositFormat):
    """codes -> (s, t, f_len, F, is_zero, is_nar); all uint32/int32 fields.

    t is the total binary exponent 2^es*K + E (int32); F the fraction field.
    """
    n, es = fmt.bits, fmt.es
    u = _u(jnp.asarray(codes)) & _mask(n)
    is_zero = u == 0
    is_nar = u == (U32(1) << U32(n - 1))
    s = _shr(u, n - 1) & U32(1)
    mag = jnp.where(s == 1, _negate_code(u, n), u)
    body = mag & _mask(n - 1)
    # regime via count-leading-(sign)bits of the body, aligned to 32 bits
    lead = _shr(body, n - 2) & U32(1)
    t_pat = jnp.where(lead == 1, body, (~body) & _mask(n - 1))
    # clz over the n-1 body bits: shift pattern's complement into the top
    r = jnp.minimum(
        _u(jax.lax.clz(_i(_shl((~t_pat) & _mask(n - 1), 32 - (n - 1))))),
        U32(n - 1),
    )
    k = jnp.where(lead == 1, _i(r) - 1, -_i(r))
    rem = jnp.maximum(_i(n - 1) - _i(r) - 1, 0)
    rest = body & _mask(rem)
    e_have = jnp.minimum(rem, es)
    e_field = _shl(_shr(rest, _u(rem - e_have)), _u(es - e_have))
    f_len = jnp.maximum(rem - es, 0)
    f_field = rest & _mask(f_len)
    t = (k << es) + _i(e_field) + fmt.bias
    return s, t, f_len, f_field, is_zero, is_nar


def decode_to_f32(codes, fmt: PositFormat):
    """Posit codes -> float32. Exact for n<=16; RNE on the fraction for n=32."""
    n = fmt.bits
    s, t, f_len, f_field, is_zero, is_nar = _decode_parts(codes, fmt)
    if n <= 16:
        man = _shl(f_field, _u(23 - f_len))  # f_len <= 13 <= 23: exact
        t_adj = t
    else:
        # f_len can reach 27 > 23: RNE into 23 mantissa bits
        cut = jnp.maximum(f_len - 23, 0)
        kept = _shr(f_field, _u(cut))
        guard = _shr(f_field, _u(jnp.maximum(cut - 1, 0))) & U32(1)
        guard = jnp.where(cut > 0, guard, U32(0))
        sticky = (f_field & _mask(jnp.maximum(cut - 1, 0))) != 0
        kept = kept + (guard & (sticky.astype(U32) | (kept & U32(1))))
        carry = _shr(kept, 23) & U32(1)  # mantissa overflow -> bump exponent
        man_full = jnp.where(carry == 1, U32(0), _shl(kept, _u(jnp.maximum(23 - f_len, 0))))
        man = jnp.where(f_len > 23, jnp.where(carry == 1, U32(0), kept & _mask(23)), man_full)
        t_adj = t + _i(carry) * jnp.where(f_len > 23, 1, 0)
    bits = _shl(s, 31) | _shl(_u(t_adj + 127), 23) | man
    val = jax.lax.bitcast_convert_type(bits, jnp.float32)
    val = jnp.where(is_zero, 0.0, val)
    val = jnp.where(is_nar, jnp.nan, val)
    return val


# ---------------------------------------------------------------------------
# Encode
# ---------------------------------------------------------------------------

def _encode_parts(s, t, frac, fw, sticky, is_zero, is_nar, fmt: PositFormat):
    """Assemble a posit code from sign, total exponent t and a fraction field.

    frac: uint32 fraction (value frac/2^fw in [0,1)); fw may be a Python int.
    Bit-exact RNE with guard/sticky; saturates to maxpos/minpos.
    """
    n, es = fmt.bits, fmt.es
    t = t - fmt.bias
    k = t >> es  # arithmetic shift: floor division by 2^es
    e_field = _u(t - (k << es))
    sat_hi = k >= n - 2  # regime fills the body (stop bit cut): >= maxpos
    sat_lo = k <= -(n - 1)
    k_c = jnp.clip(k, -(n - 2), n - 3)
    pos = k_c >= 0
    w0 = jnp.where(pos, k_c + 2, 1 - k_c)
    reg = jnp.where(pos, _shl(_mask(_u(k_c + 1)), 1), U32(1))
    avail = _i(n - 1) - w0
    ef_shift = avail + 1 - es  # fraction bits incl. guard position
    # --- case ef_shift >= 0 ---
    efp = jnp.maximum(ef_shift, 0)
    take = jnp.minimum(_u(efp), U32(fw))         # bits taken from frac
    fbits = _shl(_shr(frac, _u(fw) - take), _u(efp) - take)
    st_a = sticky | ((frac & _mask(_u(fw) - take)) != 0)
    efg_a = _shl(e_field, _u(efp)) | fbits
    # --- case ef_shift < 0 (exponent itself is cut) ---
    cut = _u(jnp.maximum(-ef_shift, 0))
    efg_b = _shr(e_field, cut)
    st_b = sticky | ((e_field & _mask(cut)) != 0) | (frac != 0)
    neg_case = ef_shift < 0
    efg = jnp.where(neg_case, efg_b, efg_a)
    st = jnp.where(neg_case, st_b, st_a)
    guard = efg & U32(1)
    kept = _shr(efg, 1)
    body = _shl(reg, _u(avail)) | kept
    body = body + (guard & (st.astype(U32) | (body & U32(1))))
    body = jnp.where(sat_hi, _mask(n - 1), body)
    body = jnp.where(sat_lo, U32(1), body)
    body = jnp.clip(body, U32(1), _mask(n - 1))  # never round to 0/NaR
    code = jnp.where(s == 1, _negate_code(body, n), body)
    code = jnp.where(is_zero, U32(0), code)
    code = jnp.where(is_nar, U32(1) << U32(n - 1), code)
    return code.astype(fmt.storage_dtype)


def encode_f32(x, fmt: PositFormat):
    """float32 -> posit codes, bit-exact RNE (quantization is exact on the
    float32 value: float32 has 23 fraction bits, all consumed losslessly)."""
    x = jnp.asarray(x, jnp.float32)
    bits = _u(jax.lax.bitcast_convert_type(x, jnp.int32))
    s = _shr(bits, 31)
    exp_raw = _i(_shr(bits, 23) & _mask(8))
    man_raw = bits & _mask(23)
    is_zero = (bits & _mask(31)) == 0
    is_nar = exp_raw == 255  # inf/nan -> NaR
    # subnormals: normalize (value = man * 2^-149)
    subn = (exp_raw == 0) & (~is_zero)
    nz_shift = _u(jax.lax.clz(_i(man_raw))) - U32(8)  # leading zeros within 23 bits
    man_n = jnp.where(subn, _shl(man_raw, nz_shift) & _mask(23), man_raw)
    t = jnp.where(subn, -126 - _i(nz_shift), exp_raw - 127)
    return _encode_parts(s, t, man_n, 23, jnp.zeros_like(is_zero), is_zero, is_nar, fmt)


# ---------------------------------------------------------------------------
# Exact arithmetic (n <= 16; int32 internals)
# ---------------------------------------------------------------------------

_FW = 14  # working fraction bits; >= max f_len (13 for P(16,0))


def _dec_norm(codes, fmt: PositFormat):
    """Decode to (s, t, mant) with mant = 1.f at _FW fraction bits."""
    s, t, f_len, f_field, is_zero, is_nar = _decode_parts(codes, fmt)
    mant = _shl(f_field, _u(_FW - f_len)) | (U32(1) << U32(_FW))
    return s, t, mant, is_zero, is_nar


def mul(a, b, fmt: PositFormat):
    """Exact posit multiply (codes x codes -> codes), n <= 16."""
    if fmt.bits > 16:
        raise NotImplementedError("exact posit arithmetic supports n<=16")
    sa, ta, ma, za, na = _dec_norm(a, fmt)
    sb, tb, mb, zb, nb = _dec_norm(b, fmt)
    s = sa ^ sb
    prod = ma * mb  # < 2^(2FW+2) = 2^30: fits uint32
    hi = _shr(prod, 2 * _FW + 1) & U32(1)
    t = ta + tb + _i(hi) - 2 * fmt.bias  # undo double bias; encode re-adds one
    pn = _shr(prod, hi)  # normalized: [2^{2FW}, 2^{2FW+1})
    frac = pn & _mask(2 * _FW)
    is_zero = za | zb
    is_nar = na | nb
    return _encode_parts(s, t, frac, 2 * _FW, jnp.zeros_like(is_zero), is_zero, is_nar, fmt)


def add(a, b, fmt: PositFormat):
    """Exact posit add (codes x codes -> codes), n <= 16.

    Classic guard/round/sticky alignment; correct RNE per posit_ref oracle
    (verified exhaustively for n=8 and by hypothesis sweeps for n=16).
    """
    if fmt.bits > 16:
        raise NotImplementedError("exact posit arithmetic supports n<=16")
    G = 3  # guard bits
    sa, ta, ma, za, na = _dec_norm(a, fmt)
    sb, tb, mb, zb, nb = _dec_norm(b, fmt)
    swap = (tb > ta) | ((tb == ta) & (mb > ma))
    sl = jnp.where(swap, sb, sa)
    ss = jnp.where(swap, sa, sb)
    tl = jnp.where(swap, tb, ta)
    ts = jnp.where(swap, ta, tb)
    ml = jnp.where(swap, mb, ma)
    ms = jnp.where(swap, ma, mb)
    d = _u(jnp.clip(tl - ts, 0, _FW + G + 2))
    mlg = _shl(ml, G)
    msg_full = _shl(ms, G)
    msg = _shr(msg_full, d)
    sticky = (msg_full & _mask(d)) != 0
    diff_sign = (sl ^ ss) == 1
    mag = jnp.where(diff_sign,
                    _i(mlg) - _i(msg) - jnp.where(sticky, 1, 0),
                    _i(mlg) + _i(msg))
    # For subtraction, borrow the sticky as a -1 so the kept bits stay a
    # *truncation* of the true result; re-express remainder as sticky below.
    res_zero = (mag == 0) & (~sticky)
    mag = jnp.maximum(mag, 1)  # keep clz defined; masked out by res_zero
    # normalize to 1.f at (FW+G) fraction bits
    msb = 31 - jax.lax.clz(mag)  # position of leading 1
    shift = msb - (_FW + G)
    mnorm = jnp.where(shift >= 0, _i(_shr(_u(mag), _u(shift))), _i(_shl(_u(mag), _u(-shift))))
    lost = jnp.where(shift > 0, (_u(mag) & _mask(_u(shift))) != 0, False)
    t = tl + shift - fmt.bias  # one bias gets re-applied in encode
    frac = _u(mnorm) & _mask(_FW + G)
    sticky = sticky | lost
    is_zero = (za & zb) | res_zero
    # one operand zero -> return the other exactly
    only_a = zb & ~za
    only_b = za & ~zb
    is_nar = na | nb
    out = _encode_parts(jnp.where(res_zero, U32(0), sl), t, frac, _FW + G,
                        sticky, is_zero, is_nar, fmt)
    a_c = jnp.asarray(a).astype(fmt.storage_dtype)
    b_c = jnp.asarray(b).astype(fmt.storage_dtype)
    out = jnp.where(only_a, a_c, out)
    out = jnp.where(only_b, b_c, out)
    return out


def sub(a, b, fmt: PositFormat):
    n = fmt.bits
    bu = _u(jnp.asarray(b))
    nb = jnp.where(bu == 0, bu, _negate_code(bu, n))  # -0 == 0; NaR negates to itself
    return add(a, nb.astype(fmt.storage_dtype), fmt)


def fma_f32(acc_f32, a_codes, b_codes, fmt: PositFormat):
    """Decode-multiply-accumulate in f32 (the TPU execution model: posit as
    storage, MXU-style compute)."""
    return acc_f32 + decode_to_f32(a_codes, fmt) * decode_to_f32(b_codes, fmt)


def dot_exact(a_codes, b_codes, fmt: PositFormat):
    """Exact posit dot product: sequential fused decode->mul->add chain in
    posit arithmetic (the TALU-V execution model).  a,b: (..., K) codes."""
    def body(carry, ab):
        ac, bc = ab
        return add(carry, mul(ac, bc, fmt), fmt), None

    a_t = jnp.moveaxis(jnp.asarray(a_codes), -1, 0)
    b_t = jnp.moveaxis(jnp.asarray(b_codes), -1, 0)
    out_shape = jnp.broadcast_shapes(a_t.shape[1:], b_t.shape[1:])
    init = jnp.zeros(out_shape, fmt.storage_dtype)
    out, _ = jax.lax.scan(body, init, (a_t, b_t))
    return out


def matmul_exact(a_codes, b_codes, fmt: PositFormat):
    """(M,K) x (K,N) exact posit matmul (TALU-V semantics, for accuracy
    experiments and small edge kernels)."""
    return dot_exact(a_codes[:, None, :], jnp.swapaxes(b_codes, 0, 1)[None, :, :], fmt)


# convenience jitted entry points ------------------------------------------

decode_to_f32_jit = jax.jit(decode_to_f32, static_argnums=1)
encode_f32_jit = jax.jit(encode_f32, static_argnums=1)
add_jit = jax.jit(add, static_argnums=2)
mul_jit = jax.jit(mul, static_argnums=2)
