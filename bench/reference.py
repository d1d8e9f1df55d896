"""The plain reference: a float32 decoder-only transformer in jax.numpy,
written from the configuration alone, and the posit rounding it needs.

It imports nothing of the program.  It reads the weights that
``bench/weights.py`` makes from the seed (the same ones the program
serves), and follows the configuration's stated semantics:

* RMSNorm ``x / rms(x) * (1 + w)``; rotary embedding on the two halves of
  each head; grouped-query attention (head h reads K/V head h // group)
  scaled by ``attention_multiplier``; SwiGLU MLP ``silu(x Wg) * (x Wu)``.
* Weights rounded to their declared posit format with a power-of-two scale
  per output channel (2 ** round(log2(mean |w|)) over the input axis,
  zeros left out of the mean), the head (``embed.T`` when tied) as well.
* A posit8 K/V cache: every K (after rotation) and V row of one head is
  stored as posit8 with the scale 2 ** floor(log2(mean |row|)).  A prompt
  attends to its own K/V at full precision within its prefill pass; every
  later token reads all K/V from the cache.
* float32 throughout, matrix products at ``highest`` precision.

Posit rounding is taken from the definition of the format: the values of
all codes are enumerated bit by bit, and x rounds to the nearest code in
the bit-string sense (the boundary between two neighbours is the value of
the odd code between them in the format one bit wider), ties to the even
code, saturating at minpos and maxpos and never to zero.
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

POSIT_FORMATS = {"posit4_1": (4, 1), "posit8_2": (8, 2), "posit16_2": (16, 2),
                 "posit4": (4, 1), "posit8": (8, 2), "posit16": (16, 2)}


# ---------------------------------------------------------------------------
# posit rounding from the definition
# ---------------------------------------------------------------------------

def posit_value(code: int, n: int, es: int) -> float:
    """The real value of an n-bit posit code with es exponent bits."""
    mask = (1 << n) - 1
    code &= mask
    if code == 0:
        return 0.0
    if code == 1 << (n - 1):
        return float("nan")
    neg = code >> (n - 1)
    if neg:
        code = (-code) & mask
    bits = [(code >> (n - 2 - i)) & 1 for i in range(n - 1)]
    run = 1
    while run < len(bits) and bits[run] == bits[0]:
        run += 1
    k = run - 1 if bits[0] else -run
    rest = bits[run + 1:]
    e_bits = (rest[:es] + [0] * es)[:es]
    e = int("".join(map(str, e_bits)), 2) if es else 0
    frac = 1.0 + sum(b * 2.0 ** -(j + 1) for j, b in enumerate(rest[es:]))
    v = 2.0 ** (k * (1 << es) + e) * frac
    return -v if neg else v


@functools.lru_cache(maxsize=None)
def posit_tables(n: int, es: int) -> Tuple[np.ndarray, np.ndarray]:
    """(values, bounds): the positive values of an (n, es) posit in order
    (code i + 1 at index i), and the rounding boundary between each
    neighbour pair, the value of code 2(i + 1) + 1 of the (n + 1)-bit
    format."""
    m = (1 << (n - 1)) - 1
    values = np.array([posit_value(c, n, es) for c in range(1, m + 1)])
    bounds = np.array([posit_value(2 * c + 1, n + 1, es)
                       for c in range(1, m)])
    assert np.all(np.diff(values) > 0) and np.all(np.diff(bounds) > 0)
    assert np.all((values[:-1] < bounds) & (bounds < values[1:]))
    return values.astype(np.float32), bounds.astype(np.float32)


def round_posit_table(x, fmt: str):
    """Round float32 ``x`` to the nearest value of posit ``fmt`` by a
    binary search of the tables (the definition, and slow on a TPU)."""
    values, bounds = posit_tables(*POSIT_FORMATS[fmt])
    values, bounds = jnp.asarray(values), jnp.asarray(bounds)
    a = jnp.abs(x)
    lo = jnp.searchsorted(bounds, a, side="left", method="scan")
    hi = jnp.searchsorted(bounds, a, side="right", method="scan")
    # a tie sits on a boundary: take the neighbour whose code is even
    # (code = index + 1)
    idx = jnp.where(lo != hi, jnp.where(lo % 2 == 1, lo, lo + 1), lo)
    return jnp.where(a == 0, 0.0, jnp.sign(x) * values[idx])


@functools.lru_cache(maxsize=None)
def _binades(n: int, es: int):
    """(first, last): the binades 2**E, E in [first, last], in which the
    posit holds every exponent bit and at least one fraction bit, so its
    values are evenly spaced there (F(E) = n - 1 - regime - es fraction
    bits) and each rounding boundary is the midpoint of its neighbours;
    None where there are none (posit4)."""
    inner = [e for e in range(-4 * n << es, 4 * n << es)
             if n - 1 - _regime(e >> es) - es >= 1]
    return (inner[0], inner[-1]) if inner else None


def _regime(k):
    """Bits of the regime of scale factor ``k`` (its run and the bit that
    ends it)."""
    return k + 2 if k >= 0 else 1 - k


def _round_in_table(a, values, bounds, offset: int):
    """Nearest of ``values`` (codes ``offset + 1``, ...) to ``a`` > 0, by
    counting the boundaries below it; a tie takes the even code."""
    b = jnp.asarray(bounds)
    lo = jnp.sum((b < a[..., None]).astype(jnp.int32), -1)
    hi = jnp.sum((b <= a[..., None]).astype(jnp.int32), -1)
    odd = (lo + offset) % 2 == 1
    idx = jnp.where(lo != hi, jnp.where(odd, lo, lo + 1), lo)
    pick = idx[..., None] == jnp.arange(len(values))
    return jnp.sum(jnp.where(pick, jnp.asarray(values), 0.0), -1)


def round_posit(x, fmt: str):
    """Round float32 ``x`` to the nearest value of posit ``fmt``: the same
    answer as ``round_posit_table``, without a gather.  Inside the binades
    where the format's values are evenly spaced, round half to even at its
    fraction bits; outside them (the few codes near minpos and maxpos)
    count the table's boundaries below |x|."""
    n, es = POSIT_FORMATS[fmt]
    values, bounds = posit_tables(n, es)
    x = jnp.asarray(x, jnp.float32)
    a = jnp.abs(x)
    span = _binades(n, es)
    if span is None:
        v = _round_in_table(a, values, bounds, 0)
        return jnp.where(a == 0, 0.0, jnp.sign(x) * v)
    first, last = span
    lo_top = int(np.searchsorted(values, 2.0 ** first))   # 2**first
    hi_bot = int(np.searchsorted(values, 2.0 ** (last + 1)))
    low = _round_in_table(a, values[:lo_top + 1], bounds[:lo_top], 0)
    high = _round_in_table(a, values[hi_bot:], bounds[hi_bot:], hi_bot)
    _, e = jnp.frexp(a)
    big_e = e - 1                                   # a in [2**E, 2**E+1)
    k = jnp.right_shift(big_e, es)
    regime = jnp.where(k >= 0, k + 2, 1 - k)
    frac = jnp.clip(n - 1 - regime - es, 1, n)
    shift = jnp.where((big_e >= first) & (big_e <= last), frac - big_e, 0)
    mid = jnp.ldexp(jnp.round(jnp.ldexp(a, shift)), -shift)
    v = jnp.where(a < 2.0 ** first, low,
                  jnp.where(a >= 2.0 ** (last + 1), high, mid))
    return jnp.where(a == 0, 0.0, jnp.sign(x) * v)


def quantize_weight(w, fmt: str):
    """Round a (in, out) weight per output column: scale 2 ** round(log2
    (mean |w|)) over the input axis, zeros left out of the mean."""
    w = w.astype(jnp.float32)
    a = jnp.abs(w)
    nz = (a > 0).astype(jnp.float32)
    mean = jnp.sum(a, axis=0, keepdims=True) / jnp.maximum(
        jnp.sum(nz, axis=0, keepdims=True), 1.0)
    s = jnp.exp2(jnp.round(jnp.log2(jnp.maximum(mean, 1e-30))))
    return round_posit(w / s, fmt) * s


def quantize_kv_rows(x, fmt: str):
    """Round (..., head_dim) K/V rows, each with scale 2 ** floor(log2
    (mean |row|))."""
    mean = jnp.maximum(jnp.mean(jnp.abs(x), axis=-1, keepdims=True), 1e-30)
    _, e = jnp.frexp(mean)                  # mean = f * 2**e, f in [.5, 1)
    s = jnp.ldexp(jnp.ones_like(mean), e - 1)
    return round_posit(x / s, fmt) * s


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _rope(x, pos, theta):
    """x (N, S, h, hd): rotate the halves (x1, x2) by angle pos * f_i."""
    half = x.shape[-1] // 2
    f = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * f[None, :]      # (S, half)
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attend(q, k, v, scale, n_prompt, kq, vq, q_block):
    """Causal GQA attention for (N, S, ...) rows.  A query at a position
    before its row's ``n_prompt`` reads the full-precision K/V (its
    prefill pass); a later one reads the cached (posit) K/V."""
    n, s, h, hd = q.shape
    g = h // k.shape[2]
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block, 1)
        qpos = i * q_block + jnp.arange(q_block)
        mask = qpos[:, None] >= kpos[None, :]

        def one(kk, vv):
            kr = jnp.repeat(kk, g, axis=2)
            vr = jnp.repeat(vv, g, axis=2)
            sc = jnp.einsum("nqhd,nkhd->nhqk", qb, kr) * scale
            sc = jnp.where(mask[None, None], sc, -jnp.inf)
            return jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(sc, -1), vr)

        cached = qpos[None, :] >= n_prompt[:, None]            # (N, qb)
        return jnp.where(cached[..., None, None], one(kq, vq), one(k, v))

    out = jax.lax.map(block, jnp.arange(s // q_block))         # (nb, N, qb..)
    return jnp.moveaxis(out, 0, 1).reshape(n, s, h, hd)


def make_reference(conf: dict):
    """Returns ``logits(weights, seqs, n_prompts) -> list of (len, vocab)
    float32 arrays``: the reference's next-token logits at every position
    of each sequence.  Runs layer by layer over all sequences, padded
    together on the right (causal, so padding changes no earlier row)."""
    m, s = conf["model"], conf["serving"]
    wf = s["weight_formats"]
    kv_fmt = s["kv_format"]
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    scale = m["attention_multiplier"]
    vocab = m["vocab_size"]

    @jax.jit
    def embed_fn(embed, tokens):
        with jax.default_matmul_precision("highest"):
            return quantize_weight(embed, wf["embed"])[tokens]

    @jax.jit
    def layer_fn(lw, x, n_prompt):
        with jax.default_matmul_precision("highest"):
            n, sl, d = x.shape
            h = _rms(x, lw["ln"].astype(jnp.float32), eps)
            q = h @ quantize_weight(lw["wq"], wf["attn"])
            k = h @ quantize_weight(lw["wk"], wf["attn"])
            v = h @ quantize_weight(lw["wv"], wf["attn"])
            pos = jnp.arange(sl)
            q = _rope(q.reshape(n, sl, nh, hd), pos, theta)
            k = _rope(k.reshape(n, sl, nkv, hd), pos, theta)
            v = v.reshape(n, sl, nkv, hd)
            kq, vq = quantize_kv_rows(k, kv_fmt), quantize_kv_rows(v, kv_fmt)
            a = _attend(q, k, v, scale, n_prompt, kq, vq, q_block=256)
            x = x + a.reshape(n, sl, nh * hd) @ quantize_weight(
                lw["wo"], wf["attn"])
            h = _rms(x, lw["ln2"].astype(jnp.float32), eps)
            gu = h @ quantize_weight(lw["wi"], wf["mlp"])
            gate, up = jnp.split(gu, 2, axis=-1)
            return x + (jax.nn.silu(gate) * up) @ quantize_weight(
                lw["wo_mlp"], wf["mlp"])

    @jax.jit
    def head_q(head):
        return quantize_weight(head, wf["head"])

    @jax.jit
    def head_fn(final_norm, qhead, x):
        with jax.default_matmul_precision("highest"):
            h = _rms(x, final_norm.astype(jnp.float32), eps)
            return (h @ qhead)[..., :vocab]

    @jax.jit
    def gap_fn(final_norm, qhead, x, tokens):
        lg = head_fn(final_norm, qhead, x)
        got = jnp.take_along_axis(lg, tokens[..., None], -1)[..., 0]
        return lg.max(-1) - got

    def logits(weights, seqs: List[np.ndarray], n_prompts: List[int],
               served: List[np.ndarray] = None, width: int = None
               ) -> List[np.ndarray]:
        """Each sequence's logits (len, vocab); or, given ``served``, each
        served token's gap below the best logit at its position, worked
        out on the device.  ``width`` (a multiple of 256) pads every call
        to one shape."""
        lens = [len(q) for q in seqs]
        width = width or -(-max(lens) // 256) * 256
        if max(lens) > width or width % 256:
            raise ValueError(f"width {width} for sequences of {max(lens)}")
        toks = np.zeros((len(seqs), width), np.int32)
        for i, q in enumerate(seqs):
            toks[i, :len(q)] = q
        x = embed_fn(weights["embed"], jnp.asarray(toks))
        npf = jnp.asarray(n_prompts, jnp.int32)
        blocks = weights["blocks"][0]
        for li in range(m["num_hidden_layers"]):
            lw = {k: v[li] for k, v in blocks.items()}
            x = layer_fn(lw, x, npf)
        qhead = head_q(weights["embed"].T if m["tie_word_embeddings"]
                       else weights["lm_head"])
        out = []
        for i, n in enumerate(lens):        # one row at a time: (S, vocab)
            if served is None:
                out.append(np.asarray(jax.device_get(head_fn(
                    weights["final_norm"], qhead, x[i:i + 1])[0, :n])))
                continue
            first = n_prompts[i] - 1
            tgt = np.zeros((1, width), np.int32)
            tgt[0, first:first + len(served[i])] = served[i]
            gaps = gap_fn(weights["final_norm"], qhead, x[i:i + 1],
                          jnp.asarray(tgt))
            out.append(np.asarray(gaps)[0, first:first + len(served[i])])
        return out

    return logits


def served_gaps(ref_logits: np.ndarray, n_prompt: int,
                served: np.ndarray) -> np.ndarray:
    """For each served token: how far its reference logit lies below the
    reference's best at the position that produced it."""
    rows = ref_logits[n_prompt - 1: n_prompt - 1 + len(served)]
    best = rows.max(-1)
    got = rows[np.arange(len(served)), served]
    return best - got
