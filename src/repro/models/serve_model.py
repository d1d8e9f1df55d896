"""Serving path: cache init, prefill, single-token decode for every family.

Caches are plain pytrees, stacked over pattern periods so decode scans over
layers exactly like training does (HLO size independent of depth):

  attn : {"k","v"}  (P, B, W, nkv, hd)   W = min(window or max_len, max_len)
  rec  : {"h"} (P, B, d), {"conv"} (P, B, K-1, d)
  ssm  : {"state"} (P, B, nh, hd, ds), {"conv"} (P, B, K-1, conv_ch)
  audio adds per-layer cross K/V over the encoder memory.

Attention writes are ring-buffered (idx = pos mod W) so sliding-window archs
(recurrentgemma) keep O(window) memory during ``long_500k`` decode while the
full-attention archs use W = max_len.  With ``policy.kv_layout == "paged"``
the per-slot rings are replaced by a shared page pool + per-sequence page
tables (``kernels/paged_kv.py``; ``cache["page_table"]`` (B, Pmax), flat
pools (R, nkv, Dc) per layer, per-slot vector ``pos``) so HBM tracks live
tokens.  ``cache["pos"]`` may be a scalar (legacy shared position) or a
(B,) per-slot vector — rope, ring/page writes and attention masks all
accept both.  The distributed decode-attention (KV-sequence sharding +
LSE combine) lives in ``repro/serve/distributed.py`` — this module is the
per-shard math it wraps.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.quant import maybe_dequant
from ..core.transprecision import BF16, KVStorage, TCPolicy, kv_storage
from ..kernels import kv_cache as kv_kernels
from ..kernels import paged_kv as paged_kernels
from . import attention, rglru as rglru_mod, ssm as ssm_mod
from .common import apply_rope, rms_norm
from .lm import ModelCfg, _mlp, _qkv, _qw, _rope_cs, forward


def _attn_w(cfg: ModelCfg, max_len: int) -> int:
    if cfg.window:
        return min(cfg.window, max_len)
    return max_len


def _kv_spec(policy: TCPolicy) -> Optional[KVStorage]:
    """Resolved KV-cache storage for ``policy`` (None = model dtype)."""
    return kv_storage(policy)


def _kv_layout(policy: TCPolicy) -> str:
    layout = getattr(policy, "kv_layout", "ring")
    if layout not in ("ring", "paged"):
        raise ValueError(f"unknown kv_layout {layout!r}; known: ring|paged")
    return layout


def init_cache(cfg: ModelCfg, batch: int, max_len: int,
               dtype=None, policy: TCPolicy = BF16, *,
               num_pages: Optional[int] = None) -> Dict[str, Any]:
    """Empty decode state for a batch of sequences up to max_len tokens.

    With a posit ``kv_format`` (or legacy ``packed_kv``) the attention K/V
    rings hold posit CODES plus per-row f32 pow2 scales (``k_scale`` /
    ``v_scale``, shape (B, W, nkv)) — the decode-on-read datapath;
    recurrent/SSM states stay full precision (rewritten every step).

    With ``policy.kv_layout == "paged"`` the per-slot rings are replaced
    by a shared flat page pool (R = num_pages * kv_page_size rows, no
    batch axis) plus a top-level ``page_table`` (B, Pmax) and per-slot
    vector ``pos``.  ``num_pages=None`` fully reserves (1 trash page +
    batch * Pmax) and installs the identity table, so standalone
    prefill/decode works without an allocator; an engine passes its own
    (smaller) pool size and manages the table itself."""
    spec = _kv_spec(policy)
    posit_kv = spec is not None and spec.is_posit
    paged = _kv_layout(policy) == "paged"
    if posit_kv:
        dt = dtype or cfg.dtype            # cross-K/V, memory stay float
        kv_ch = kv_kernels.code_channels(cfg.head_dim, spec.fmt, spec.packed)
    else:
        dt = dtype or (spec.dtype if spec is not None else cfg.dtype)
    hd, nkv = cfg.head_dim, cfg.n_kv_heads
    w = _attn_w(cfg, max_len)
    if paged:
        if cfg.window:
            raise ValueError("paged KV layout does not support sliding-"
                             "window attention; use kv_layout='ring'")
        ps = policy.kv_page_size
        pmax = -(-max_len // ps)           # logical pages per slot
        full_pool = num_pages is None
        if full_pool:
            num_pages = 1 + batch * pmax   # page 0 is the trash page
        pool_rows = num_pages * ps
    d_in = cfg.ssm_expand * cfg.d_model
    nh_ssm = d_in // cfg.ssm_headdim
    conv_ch = d_in + 2 * cfg.ssm_groups * cfg.ssm_state

    def block_cache(btype: str, stacked: int):
        def z(shape, dtype=dt):
            s = (stacked,) + shape if stacked else shape
            return jnp.zeros(s, dtype)
        if btype == "attn":
            if paged:
                kv_dt = spec.fmt.storage_dtype if posit_kv else dt
                c = {"k": z((pool_rows, nkv, kv_ch if posit_kv else hd),
                            kv_dt),
                     "v": z((pool_rows, nkv, kv_ch if posit_kv else hd),
                            kv_dt)}
                if posit_kv:
                    c["k_scale"] = z((pool_rows, nkv), jnp.float32) + 1.0
                    c["v_scale"] = z((pool_rows, nkv), jnp.float32) + 1.0
            elif posit_kv:
                c = {"k": z((batch, w, nkv, kv_ch), spec.fmt.storage_dtype),
                     "v": z((batch, w, nkv, kv_ch), spec.fmt.storage_dtype),
                     "k_scale": z((batch, w, nkv), jnp.float32) + 1.0,
                     "v_scale": z((batch, w, nkv), jnp.float32) + 1.0}
            else:
                c = {"k": z((batch, w, nkv, hd)), "v": z((batch, w, nkv, hd))}
            if cfg.family == "audio":
                # cross K/V stay unpacked (written once at prefill)
                c["xk"] = z((batch, cfg.enc_seq, nkv, hd), cfg.dtype)
                c["xv"] = z((batch, cfg.enc_seq, nkv, hd), cfg.dtype)
            return c
        if btype == "rec":
            return {"h": z((batch, cfg.d_model), jnp.float32),
                    "conv": z((batch, cfg.conv_kernel - 1, cfg.d_model),
                              cfg.dtype)}
        if btype == "ssm":
            return {"state": z((batch, nh_ssm, cfg.ssm_headdim, cfg.ssm_state),
                               jnp.float32),
                    "conv": z((batch, cfg.conv_kernel - 1, conv_ch),
                              cfg.dtype)}
        raise ValueError(btype)

    cache: Dict[str, Any] = {
        # paged serving needs true per-slot positions; ring keeps the
        # legacy scalar for existing single-sequence callers (both shapes
        # are supported throughout the decode path)
        "pos": jnp.zeros((batch,) if paged else (), jnp.int32),
        "blocks": tuple(block_cache(t, cfg.n_periods) for t in cfg.period),
    }
    if paged:
        if full_pool:   # identity table: slot i owns pages 1+i*pmax ..
            table = 1 + jnp.arange(batch * pmax, dtype=jnp.int32).reshape(
                batch, pmax)
        else:           # caller (engine/allocator) manages the table
            table = jnp.zeros((batch, pmax), jnp.int32)
        cache["page_table"] = table
    if cfg.n_tail:
        tail_types = cfg.block_types[cfg.n_periods * len(cfg.period):]
        cache["tail"] = tuple(block_cache(t, 0) for t in tail_types)
    if cfg.family == "audio":
        cache["memory"] = jnp.zeros((batch, cfg.enc_seq, cfg.d_model), dt)
    return cache


# ---------------------------------------------------------------------------
# Per-block decode steps
# ---------------------------------------------------------------------------

def _ring_write(buf, val, pos):
    """buf: (B, W, ...); val: (B, 1, ...); write at pos mod W.
    ``pos`` scalar (shared) or (B,) per-slot."""
    w = buf.shape[1]
    pos = jnp.asarray(pos)
    if pos.ndim:
        return buf.at[jnp.arange(buf.shape[0]), pos % w].set(
            val[:, 0].astype(buf.dtype))
    return jax.lax.dynamic_update_slice_in_dim(buf, val.astype(buf.dtype),
                                               pos % w, axis=1)


def _ring_append_packed(c, kp, vp, pos, spec: KVStorage):
    """Encode-on-write ring append for a posit-packed cache block.

    Pallas ``kv_append`` on accelerators; bit-identical pure-jnp reference
    on CPU (the kernel's interpret-mode overhead is per-layer-per-step)."""
    args = (c["k"], c["k_scale"], c["v"], c["v_scale"],
            kp.astype(jnp.float32), vp.astype(jnp.float32), pos)
    if jax.default_backend() == "cpu":
        return kv_kernels.kv_append_ref(*args, spec.fmt, spec.packed)
    return kv_kernels.kv_append(*args, spec.fmt, packed=spec.packed)


def _paged_append_packed(c, kp, vp, dst, spec: KVStorage):
    """Encode-on-write append into the paged pool (Pallas on accelerators,
    bit-identical pure-jnp reference on CPU)."""
    args = (c["k"], c["k_scale"], c["v"], c["v_scale"],
            kp.astype(jnp.float32), vp.astype(jnp.float32), dst)
    if jax.default_backend() == "cpu":
        return paged_kernels.paged_kv_append_ref(*args, spec.fmt, spec.packed)
    return paged_kernels.paged_kv_append(*args, spec.fmt, packed=spec.packed)


def _attn_decode_paged(c, cfg, policy, pos, qp, kp, vp, table, attn_impl):
    """Paged-pool K/V append + page-walking attention for one layer.

    ``pos`` must be a (B,) per-slot vector; ``c["k"]``/``c["v"]`` are flat
    pools (R, nkv, Dc|hd) shared by all slots; ``table`` is the top-level
    (B, Pmax) page table (shared across layers, closed over by the layer
    scan)."""
    spec = _kv_spec(policy)
    posit_kv = spec is not None and spec.is_posit
    ps = policy.kv_page_size
    dst = paged_kernels.flat_dst_rows(table, pos, ps)
    seq_lens = pos + 1
    new_c = {}
    if posit_kv:
        kc, ks, vc, vs = _paged_append_packed(c, kp, vp, dst, spec)
        if attn_impl is not None and getattr(attn_impl, "paged_kv", False):
            # paged protocol: pool codes + scales + the page table cross
            # the impl boundary (the distributed path ships all three)
            ao = attn_impl(qp, kc, vc, seq_lens, k_scale=ks, v_scale=vs,
                           kv_spec=spec, page_table=table, page_size=ps)
        elif attn_impl is not None:
            k_read = paged_kernels.gather_decode_pages(
                kc, ks, table, ps, spec.fmt, spec.packed)
            v_read = paged_kernels.gather_decode_pages(
                vc, vs, table, ps, spec.fmt, spec.packed)
            ao = attn_impl(qp, k_read, v_read, seq_lens)
        else:
            # an idle slot holds no page (its table row starts at the
            # trash page) while its position runs on: it attends to no
            # row, so the page walk skips it
            live = jnp.where(table[:, 0] > 0, seq_lens, 0)
            attend = (paged_kernels.paged_decode_attention_ref
                      if jax.default_backend() == "cpu"
                      else paged_kernels.paged_decode_attention)
            ao = attend(qp, kc, ks, vc, vs, table, live, spec.fmt,
                        page_size=ps, packed=spec.packed)
        new_c.update(k=kc, v=vc, k_scale=ks, v_scale=vs)
    else:
        kc = c["k"].at[dst].set(kp[:, 0].astype(c["k"].dtype))
        vc = c["v"].at[dst].set(vp[:, 0].astype(c["v"].dtype))
        k_read = paged_kernels.gather_pages(kc, table, ps)
        v_read = paged_kernels.gather_pages(vc, table, ps)
        attn_fn = attn_impl or attention.decode_attention
        ao = attn_fn(qp, k_read, v_read, seq_lens)
        new_c.update(k=kc, v=vc)
    return ao, new_c


def _attn_decode(p, c, x, cfg, policy, pos, memory=None, attn_impl=None,
                 page_table=None):
    b = x.shape[0]
    spec = _kv_spec(policy)
    posit_kv = spec is not None and spec.is_posit
    paged = page_table is not None
    pos = jnp.asarray(pos)
    if paged and pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (b,))
    h = rms_norm(x, p["ln"])
    qp, kp, vp = _qkv(p, h, cfg, policy)
    if pos.ndim:                       # per-slot positions: (B, 1) rope
        posv = pos[:, None]
    else:
        posv = jnp.full((b, 1), pos) if cfg.mrope else pos[None]
    cos, sin = _rope_cs(cfg, posv)
    qp = apply_rope(qp, cos, sin)
    kp = apply_rope(kp, cos, sin)
    new_c = dict(c)
    if paged:
        ao, nc = _attn_decode_paged(c, cfg, policy, pos, qp, kp, vp,
                                    page_table, attn_impl)
        new_c.update(nc)
    elif posit_kv:
        kc, ks, vc, vs = _ring_append_packed(c, kp, vp, pos, spec)
        w = kc.shape[1]
        cl = jnp.minimum(pos + 1, w)
        if attn_impl is not None and getattr(attn_impl, "packed_kv", False):
            # packed protocol: codes + scales cross the impl boundary
            ao = attn_impl(qp, kc, vc, cl, k_scale=ks, v_scale=vs,
                           kv_spec=spec)
        elif attn_impl is not None:
            k_read = kv_kernels.decode_kv_rows(kc, ks[..., None], spec.fmt,
                                               spec.packed)
            v_read = kv_kernels.decode_kv_rows(vc, vs[..., None], spec.fmt,
                                               spec.packed)
            ao = attn_impl(qp, k_read, v_read, cl)
        else:
            ao = attention.decode_attention_packed(
                qp, kc, vc, cl, k_scale=ks, v_scale=vs, spec=spec)
        new_c.update(k=kc, v=vc, k_scale=ks, v_scale=vs)
    else:
        k_cache = _ring_write(c["k"], kp, pos)
        v_cache = _ring_write(c["v"], vp, pos)
        w = k_cache.shape[1]
        attn_fn = attn_impl or attention.decode_attention
        ao = attn_fn(qp, k_cache, v_cache, jnp.minimum(pos + 1, w))
        new_c["k"], new_c["v"] = k_cache, v_cache
    # attention may run at higher precision than the stream (f32-decoded
    # K/V); the residual stream keeps the model dtype for the scan carry
    x = x + jnp.einsum("bsk,kd->bsd", ao.reshape(b, 1, -1),
                       _qw(policy, "attn_weights")(p["wo"])).astype(x.dtype)
    if memory is not None:
        hx = rms_norm(x, p["ln_x"])
        qx = jnp.einsum("bsd,dk->bsk", hx, maybe_dequant(p["wq_x"])).reshape(
            b, 1, cfg.n_heads, cfg.head_dim)
        xo = attention.decode_attention(qx, c["xk"], c["xv"], c["xk"].shape[1])
        x = x + jnp.einsum("bsk,kd->bsd", xo.reshape(b, 1, -1), maybe_dequant(p["wo_x"]))
    h2 = rms_norm(x, p["ln2"])
    if cfg.family == "moe":
        from . import moe as moe_mod
        mo, _ = moe_mod.moe_ffn(p["moe"], h2, top_k=cfg.moe_topk,
                                capacity_factor=cfg.capacity_factor,
                                quantize_w=_qw(policy, "mlp_weights"))
    else:
        mo = _mlp(p, h2, cfg, policy)
    return x + mo, new_c


def _rec_decode(p, c, x, cfg, policy):
    b = x.shape[0]
    h = rms_norm(x, p["ln"])
    gate = jax.nn.gelu(jnp.einsum("bsd,dk->bsk", h, maybe_dequant(p["wy"])))
    u = jnp.einsum("bsd,dk->bsk", h, maybe_dequant(p["wx"]))
    window = jnp.concatenate([c["conv"], u.astype(c["conv"].dtype)], axis=1)
    k = cfg.conv_kernel
    u = sum(window[:, i:i + 1] * p["conv_w"][i] for i in range(k))
    y, h_new = rglru_mod.rglru_step(p["rglru"], u, c["h"])
    x = x + jnp.einsum("bsk,kd->bsd", y * gate, maybe_dequant(p["w_out"]))
    x = x + _mlp(p, rms_norm(x, p["ln2"]), cfg, policy)
    return x, {"h": h_new, "conv": window[:, 1:]}


def _ssm_decode(p, c, x, cfg, policy):
    h = rms_norm(x, p["ln"])
    y, (conv_state, ssm_state) = ssm_mod.mamba2_layer(
        p, h, cfg, conv_state=c["conv"], ssm_state=c["state"],
        quantize_w=_qw(policy, "mlp_weights"))
    return x + y, {"state": ssm_state, "conv": conv_state}


def _block_decode(btype, p, c, x, cfg, policy, pos, memory=None,
                  attn_impl=None, page_table=None):
    if btype == "attn":
        return _attn_decode(p, c, x, cfg, policy, pos, memory=memory,
                            attn_impl=attn_impl, page_table=page_table)
    if btype == "rec":
        return _rec_decode(p, c, x, cfg, policy)
    if btype == "ssm":
        return _ssm_decode(p, c, x, cfg, policy)
    raise ValueError(btype)


def decode_step(params, cache, tokens, cfg: ModelCfg,
                policy: TCPolicy = BF16,
                embeds: Optional[jax.Array] = None,
                attn_impl=None):
    """One serving step. tokens: (B, 1) int32 (or embeds (B, 1, d) for vlm).
    Returns (logits (B, vocab_pad), new_cache)."""
    pos = cache["pos"]
    page_table = cache.get("page_table")
    if embeds is not None:
        x = embeds.astype(cfg.dtype)
    else:
        emb = policy.quantize_weight(params["embed"], "embed_weights")
        x = emb[tokens].astype(cfg.dtype)
    memory = cache.get("memory") if cfg.family == "audio" else None

    def scan_body(carry, pc):
        x = carry
        pparams, pcache = pc
        new_caches = []
        for i, btype in enumerate(cfg.period):
            x, nc = _block_decode(btype, pparams[i], pcache[i], x, cfg,
                                  policy, pos, memory=memory,
                                  attn_impl=attn_impl,
                                  page_table=page_table)
            new_caches.append(nc)
        return x, tuple(new_caches)

    x, new_blocks = jax.lax.scan(scan_body, x,
                                 (params["blocks"], cache["blocks"]))
    new_cache = dict(cache)
    new_cache["blocks"] = new_blocks
    if cfg.n_tail:
        tail_types = cfg.block_types[cfg.n_periods * len(cfg.period):]
        new_tail = []
        for p_i, c_i, btype in zip(params["tail"], cache["tail"], tail_types):
            x, nc = _block_decode(btype, p_i, c_i, x, cfg, policy, pos,
                                  memory=memory, attn_impl=attn_impl,
                                  page_table=page_table)
            new_tail.append(nc)
        new_cache["tail"] = tuple(new_tail)
    x = rms_norm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embed else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(cfg.dtype))[:, 0]
    new_cache["pos"] = pos + 1
    return logits, new_cache


# ---------------------------------------------------------------------------
# verify_step: multi-token chunk decode (speculative verify)
# ---------------------------------------------------------------------------

def _ring_write_rows(buf, val, pos):
    """buf: (B, W, ...); val: (B, T, ...); row t of slot b lands at
    (pos[b] + t) mod W.  ``pos`` is the (B,) per-slot start position."""
    b, w = buf.shape[:2]
    t = val.shape[1]
    idx = (jnp.asarray(pos, jnp.int32)[:, None]
           + jnp.arange(t, dtype=jnp.int32)[None, :]) % w
    return buf.at[jnp.arange(b)[:, None], idx].set(val.astype(buf.dtype))


def _ring_append_rows_packed(c, kp, vp, pos, spec: KVStorage):
    """Chunked encode-on-write ring append (Pallas on accelerators,
    bit-identical pure-jnp reference on CPU)."""
    args = (c["k"], c["k_scale"], c["v"], c["v_scale"],
            kp.astype(jnp.float32), vp.astype(jnp.float32), pos)
    if jax.default_backend() == "cpu":
        return kv_kernels.kv_append_rows_ref(*args, spec.fmt, spec.packed)
    return kv_kernels.kv_append_rows(*args, spec.fmt, packed=spec.packed)


def _paged_append_rows_packed(c, kp, vp, dst, spec: KVStorage):
    """Chunked encode-on-write append into the paged pool."""
    args = (c["k"], c["k_scale"], c["v"], c["v_scale"],
            kp.astype(jnp.float32), vp.astype(jnp.float32), dst)
    if jax.default_backend() == "cpu":
        return paged_kernels.paged_kv_append_rows_ref(*args, spec.fmt,
                                                      spec.packed)
    return paged_kernels.paged_kv_append_rows(*args, spec.fmt,
                                              packed=spec.packed)


def _attn_verify(p, c, x, cfg, policy, pos, page_table=None):
    """One attention layer of the T-token verify pass.

    Appends the chunk's T K/V rows (positions pos..pos+T-1 per slot) to
    the cache, then runs chunked causal attention against it.  Every
    per-token operation reuses the decode-path building blocks on a T
    axis, so the logits (and the cache rows written) are bit-identical to
    feeding the chunk through ``decode_step`` one token at a time on the
    CPU/reference backend (the one CI pins).  On accelerators the
    single-token path reads through the fused Pallas kernels while this
    chunk path reads through gather+decode XLA attention — a different
    summation order; the fused chunk kernel is a ROADMAP follow-on."""
    b, t = x.shape[:2]
    spec = _kv_spec(policy)
    posit_kv = spec is not None and spec.is_posit
    paged = page_table is not None
    pos = jnp.asarray(pos)
    h = rms_norm(x, p["ln"])
    qp, kp, vp = _qkv(p, h, cfg, policy)
    posv = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]  # (B, T)
    cos, sin = _rope_cs(cfg, posv)
    qp = apply_rope(qp, cos, sin)
    kp = apply_rope(kp, cos, sin)
    new_c = dict(c)
    if paged:
        ps = policy.kv_page_size
        dst = paged_kernels.flat_dst_rows_chunk(page_table, pos, t, ps)
        if posit_kv:
            kc, ks, vc, vs = _paged_append_rows_packed(c, kp, vp, dst, spec)
            k_read = paged_kernels.gather_decode_pages(
                kc, ks, page_table, ps, spec.fmt, spec.packed)
            v_read = paged_kernels.gather_decode_pages(
                vc, vs, page_table, ps, spec.fmt, spec.packed)
            new_c.update(k=kc, v=vc, k_scale=ks, v_scale=vs)
        else:
            kc = c["k"].at[dst].set(kp.astype(c["k"].dtype))
            vc = c["v"].at[dst].set(vp.astype(c["v"].dtype))
            k_read = paged_kernels.gather_pages(kc, page_table, ps)
            v_read = paged_kernels.gather_pages(vc, page_table, ps)
            new_c.update(k=kc, v=vc)
    elif posit_kv:
        kc, ks, vc, vs = _ring_append_rows_packed(c, kp, vp, pos, spec)
        k_read = kv_kernels.decode_kv_rows(kc, ks[..., None], spec.fmt,
                                           spec.packed)
        v_read = kv_kernels.decode_kv_rows(vc, vs[..., None], spec.fmt,
                                           spec.packed)
        new_c.update(k=kc, v=vc, k_scale=ks, v_scale=vs)
    else:
        kc = _ring_write_rows(c["k"], kp, pos)
        vc = _ring_write_rows(c["v"], vp, pos)
        k_read, v_read = kc, vc
        new_c.update(k=kc, v=vc)
    ao = attention.chunk_decode_attention(qp, k_read, v_read, posv)
    x = x + jnp.einsum("bsk,kd->bsd", ao.reshape(b, t, -1),
                       _qw(policy, "attn_weights")(p["wo"])).astype(x.dtype)
    h2 = rms_norm(x, p["ln2"])
    return x + _mlp(p, h2, cfg, policy), new_c


def verify_step(params, cache, tokens, cfg: ModelCfg,
                policy: TCPolicy = BF16):
    """Multi-token verify pass: decode a (B, T) token chunk in ONE model
    call with per-slot positions — the target-precision half of
    self-speculative decoding.

    tokens: (B, T) int32 — token t of slot b is scored *and* its K/V row
    written at position cache["pos"][b] + t.  Returns (logits
    (B, T, vocab_pad), new_cache) with ``pos`` advanced by T; the caller
    commits accepted tokens and rolls the cache back past the first
    rejection (``serve/speculative.py``).

    Supports attention-only stacks (every token writes exactly one cache
    row, so rollback is a row rewind); recurrent/SSM/MoE/audio families
    would need state snapshots and are rejected.
    """
    if any(bt != "attn" for bt in cfg.block_types):
        raise ValueError("verify_step supports attention-only stacks; "
                         f"{cfg.name} has blocks {set(cfg.block_types)}")
    if cfg.family == "moe":
        raise ValueError("verify_step does not support MoE stacks (chunked "
                         "dispatch changes capacity routing vs per-token)")
    if cfg.family == "audio":
        raise ValueError("verify_step does not support encoder-decoder "
                         "stacks (no cross-attention in the chunk path)")
    if cfg.window:
        raise ValueError("verify_step does not support sliding-window "
                         "attention (rollback assumes no ring wraparound)")
    b, t = tokens.shape
    pos = jnp.broadcast_to(jnp.asarray(cache["pos"], jnp.int32), (b,))
    page_table = cache.get("page_table")
    emb = policy.quantize_weight(params["embed"], "embed_weights")
    x = emb[tokens].astype(cfg.dtype)

    def scan_body(carry, pc):
        x = carry
        pparams, pcache = pc
        new_caches = []
        for i, _ in enumerate(cfg.period):
            x, nc = _attn_verify(pparams[i], pcache[i], x, cfg, policy, pos,
                                 page_table=page_table)
            new_caches.append(nc)
        return x, tuple(new_caches)

    x, new_blocks = jax.lax.scan(scan_body, x,
                                 (params["blocks"], cache["blocks"]))
    new_cache = dict(cache)
    new_cache["blocks"] = new_blocks
    if cfg.n_tail:
        new_tail = []
        for p_i, c_i in zip(params["tail"], cache["tail"]):
            x, nc = _attn_verify(p_i, c_i, x, cfg, policy, pos,
                                 page_table=page_table)
            new_tail.append(nc)
        new_cache["tail"] = tuple(new_tail)
    x = rms_norm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embed else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(cfg.dtype))
    new_cache["pos"] = cache["pos"] + t
    return logits, new_cache


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def prefill(params, batch, cfg: ModelCfg, max_len: int,
            policy: TCPolicy = BF16, true_len=None):
    """Run the prompt through the model, returning (last_logits, cache).

    Functionally: forward() for the logits + a second pass's worth of cache
    construction fused into the same stack traversal.

    ``true_len`` (scalar or (B,) int32) enables right-padded *bucketed*
    prefill: ``batch["tokens"]`` is padded to a shared bucket width S and
    only the first ``true_len[b]`` tokens of each row are real.  Padding
    rows are causally masked out of every real row's attention (exact-zero
    contributions, so real logits are bit-identical to an unpadded
    prefill), their K/V rows are written as cache-init values (paged: to
    the trash row), logits come from position ``true_len - 1`` per row,
    and ``cache["pos"]`` is the per-slot ``true_len`` vector.  Only
    attention-only stacks support this (recurrent/SSM carries and MoE
    capacity routing are position-dependent under padding).
    """
    from .lm import _attn_block, _rec_block, _ssm_block  # local reuse
    if cfg.family == "vlm" and "embeds" in batch:
        x = batch["embeds"].astype(cfg.dtype)
        b, s = x.shape[0], x.shape[1]
    else:
        tokens = batch["tokens"]
        b, s = tokens.shape
        emb = policy.quantize_weight(params["embed"], "embed_weights")
        x = emb[tokens].astype(cfg.dtype)
    valid = None
    if true_len is not None:
        if (any(bt != "attn" for bt in cfg.block_types) or cfg.window
                or cfg.family in ("moe", "audio")
                or ("embeds" in batch and cfg.family == "vlm")):
            raise ValueError(
                "bucketed prefill (true_len) needs a decoder-only "
                "attention stack without MoE, sliding windows or "
                f"cross/vision inputs; {cfg.name} is not one")
        true_len = jnp.broadcast_to(
            jnp.asarray(true_len, jnp.int32).reshape(-1), (b,))
        valid = jnp.arange(s, dtype=jnp.int32)[None, :] < true_len[:, None]
    cache = init_cache(cfg, b, max_len, policy=policy)
    spec = _kv_spec(policy)
    posit_kv = spec is not None and spec.is_posit
    paged = _kv_layout(policy) == "paged"
    if paged and s > max_len:
        raise ValueError(f"prompt length {s} exceeds max_len {max_len} "
                         "for the paged KV layout")
    w = _attn_w(cfg, max_len)
    memory = None
    if cfg.family == "audio":
        from .lm import _encode_audio
        memory = _encode_audio(params, batch["frames"], cfg, policy)
        cache["memory"] = memory

    start = max(s - w, 0)
    length = min(s, w)
    ring_idx = (start + jnp.arange(length)) % w
    if paged:
        # per-slot flat pool rows for prompt positions 0..s-1; padding
        # rows (bucketed prefill) land on the trash row 0 instead
        ps = policy.kv_page_size
        tok_idx = jnp.arange(s)
        rows2d = (cache["page_table"][:, tok_idx // ps] * ps
                  + (tok_idx % ps)[None, :])                     # (b, s)
        if valid is not None:
            rows2d = jnp.where(valid, rows2d, 0)
        flat_rows = rows2d.reshape(-1)                           # (b*s,)

    def fill(buf, kv):
        rows = kv[:, start:start + length]
        if valid is not None:   # padding rows hold cache-init zeros
            rows = jnp.where(valid[:, start:start + length, None, None],
                             rows, 0)
        return buf.at[:, ring_idx].set(rows.astype(buf.dtype))

    def fill_paged(nc, c_i, name, kv):
        """Bulk write of the prompt's K/V rows into the page pool."""
        if posit_kv:
            codes, scale = kv_kernels.encode_kv_rows(
                kv.astype(jnp.float32), spec.fmt, spec.packed)
            nc[name] = c_i[name].at[flat_rows].set(
                codes.reshape((b * s,) + codes.shape[2:]).astype(
                    c_i[name].dtype))
            nc[name + "_scale"] = c_i[name + "_scale"].at[flat_rows].set(
                scale[..., 0].reshape(b * s, -1))
        else:
            nc[name] = c_i[name].at[flat_rows].set(
                kv.reshape((b * s,) + kv.shape[2:]).astype(c_i[name].dtype))

    def fill_packed(nc, c_i, name, kv):
        """Bulk encode-on-write of the prompt's K/V rows into the ring."""
        codes, scale = kv_kernels.encode_kv_rows(
            kv[:, start:start + length].astype(jnp.float32),
            spec.fmt, spec.packed)
        if valid is not None:   # padding rows hold cache-init codes/scales
            vm = valid[:, start:start + length, None, None]
            codes = jnp.where(vm, codes, 0)
            scale = jnp.where(vm, scale, 1.0)
        nc[name] = c_i[name].at[:, ring_idx].set(
            codes.astype(c_i[name].dtype))
        nc[name + "_scale"] = c_i[name + "_scale"].at[:, ring_idx].set(
            scale[..., 0])

    def run_block(btype, p_i, c_i, x):
        if btype == "attn":
            h = rms_norm(x, p_i["ln"])
            qp, kp, vp = _qkv(p_i, h, cfg, policy)
            pos = jnp.arange(s)
            cos, sin = _rope_cs(cfg, pos[None, :].repeat(b, 0)) if cfg.mrope \
                else _rope_cs(cfg, pos)
            qp = apply_rope(qp, cos, sin)
            kp = apply_rope(kp, cos, sin)
            ao = attention.blockwise_attention(
                qp, kp, vp, causal=True,
                window=cfg.window if cfg.family == "hybrid" or cfg.window else None,
                q_block=cfg.q_block, kv_block=cfg.kv_block)
            x = x + jnp.einsum("bsk,kd->bsd", ao.reshape(b, s, -1),
                               _qw(policy, "attn_weights")(p_i["wo"]))
            nc = dict(c_i)
            if paged:
                fill_paged(nc, c_i, "k", kp)
                fill_paged(nc, c_i, "v", vp)
            elif posit_kv:
                fill_packed(nc, c_i, "k", kp)
                fill_packed(nc, c_i, "v", vp)
            else:
                nc["k"] = fill(c_i["k"], kp)
                nc["v"] = fill(c_i["v"], vp)
            if memory is not None:
                hx = rms_norm(x, p_i["ln_x"])
                qx = jnp.einsum("bsd,dk->bsk", hx, p_i["wq_x"]).reshape(
                    b, s, cfg.n_heads, cfg.head_dim)
                kx = jnp.einsum("bsd,dk->bsk", memory, p_i["wk_x"]).reshape(
                    b, memory.shape[1], cfg.n_kv_heads, cfg.head_dim)
                vx = jnp.einsum("bsd,dk->bsk", memory, p_i["wv_x"]).reshape(
                    b, memory.shape[1], cfg.n_kv_heads, cfg.head_dim)
                xo = attention.blockwise_attention(qx, kx, vx, causal=False,
                                                   q_block=cfg.q_block,
                                                   kv_block=cfg.kv_block)
                x = x + jnp.einsum("bsk,kd->bsd", xo.reshape(b, s, -1),
                                   p_i["wo_x"])
                nc["xk"], nc["xv"] = kx.astype(nc["xk"].dtype), vx.astype(nc["xv"].dtype)
            h2 = rms_norm(x, p_i["ln2"])
            if cfg.family == "moe":
                from . import moe as moe_mod
                mo, _ = moe_mod.moe_ffn(p_i["moe"], h2, top_k=cfg.moe_topk,
                                        capacity_factor=cfg.capacity_factor,
                                        quantize_w=_qw(policy, "mlp_weights"))
            else:
                mo = _mlp(p_i, h2, cfg, policy)
            return x + mo, nc
        if btype == "rec":
            # track conv tail (raw u) + final hidden state
            h = rms_norm(x, p_i["ln"])
            u_raw = jnp.einsum("bsd,dk->bsk", h, p_i["wx"])
            x, h_last = _rec_block(p_i, x, cfg, policy)
            k = cfg.conv_kernel
            pad = jnp.pad(u_raw, ((0, 0), (k - 1, 0), (0, 0)))
            return x, {"h": h_last.astype(jnp.float32),
                       "conv": pad[:, -(k - 1):].astype(cfg.dtype)}
        if btype == "ssm":
            h = rms_norm(x, p_i["ln"])
            from .ssm import _split_streams
            w_in = _qw(policy, "mlp_weights")(p_i["in_proj"])
            zxbcdt = jnp.einsum("bsd,dk->bsk", h, w_in)
            _, xBC_raw, _ = _split_streams(zxbcdt, cfg)
            y, (_, ssm_state) = ssm_mod.mamba2_layer(
                p_i, h, cfg, quantize_w=_qw(policy, "mlp_weights"))
            k = cfg.conv_kernel
            pad = jnp.pad(xBC_raw, ((0, 0), (k - 1, 0), (0, 0)))
            return x + y.astype(x.dtype), {
                "state": ssm_state,
                "conv": pad[:, -(k - 1):].astype(cfg.dtype)}
        raise ValueError(btype)

    def scan_body(carry, pc):
        x = carry
        pparams, pcache = pc
        ncs = []
        for i, btype in enumerate(cfg.period):
            x, nc = run_block(btype, pparams[i], pcache[i], x)
            ncs.append(nc)
        return x, tuple(ncs)

    x, new_blocks = jax.lax.scan(scan_body, x,
                                 (params["blocks"], cache["blocks"]))
    cache["blocks"] = new_blocks
    if cfg.n_tail:
        tail_types = cfg.block_types[cfg.n_periods * len(cfg.period):]
        new_tail = []
        for p_i, c_i, btype in zip(params["tail"], cache["tail"], tail_types):
            x, nc = run_block(btype, p_i, c_i, x)
            new_tail.append(nc)
        cache["tail"] = tuple(new_tail)
    x = rms_norm(x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embed else params["lm_head"]
    x_last = (x[:, -1] if true_len is None
              else x[jnp.arange(b), true_len - 1])
    logits = jnp.einsum("bd,dv->bv", x_last, head.astype(cfg.dtype))
    if true_len is not None:
        cache["pos"] = true_len
    else:
        cache["pos"] = (jnp.full((b,), s, jnp.int32) if paged
                        else jnp.asarray(s, jnp.int32))
    return logits, cache
