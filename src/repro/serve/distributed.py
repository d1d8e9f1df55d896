"""Distributed decode attention: KV-sequence sharding + log-sum-exp combine.

The decode cells keep a KV cache of up to 512k tokens; sharding its sequence
axis over "model" is the only way it fits, but a naive softmax over a
sharded axis makes XLA all-gather the WHOLE cache every token
(O(B*W*nkv*hd) ICI bytes — the dominant collective in the baseline
dry-run).  The fix is the classic distributed-softmax identity: each shard
reduces its local slice to

    (m_i = max_s, l_i = sum exp(s - m_i), o_i = sum exp(s - m_i) v)

and the combine is an O(B*nh*hd) psum:

    m = pmax(m_i);  out = psum(o_i * e^{m_i - m}) / psum(l_i * e^{m_i - m})

Collective volume drops from O(KV-cache) to O(one activation row) —
independent of sequence length.  This is the TPU-native analogue of the
paper's TALU-V: many small units each owning a slice of the operand vector,
combined with a tree reduction.

Implemented with ``shard_map`` manual over "model" only (data/pod stay
automatic), so it composes with the pjit-sharded rest of the decode step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models import serve_model
from ..models.attention import NEG_INF


def _local_lse(q, k, v, start, cache_len):
    """Partial attention over a local KV slice.

    q: (B, 1, nkv, grp, hd); k/v: (B, Wl, nkv, hd); start: global index of
    this slice; cache_len scalar (shared) or (B,) per-slot.  Returns
    (o (B,nkv,grp,hd), l (B,nkv,grp), m (B,nkv,grp)).
    """
    b, wl = k.shape[0], k.shape[1]
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32)[..., 0, :]
    idx = start + jnp.arange(wl)
    cl = jnp.broadcast_to(jnp.asarray(cache_len), (b,))
    valid = idx[None, :] < cl[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    m = scores.max(-1)                                    # (B, nkv, grp)
    p = jnp.exp(scores - m[..., None])
    l = p.sum(-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p.astype(v.dtype), v).astype(jnp.float32)
    return o, l, m


def distributed_decode_attention(mesh: Mesh, axis: str = "model",
                                 kv_spec=None, *, paged: bool = False,
                                 page_size: int = 16):
    """Returns an ``attn_impl(q, k_cache, v_cache, cache_len)`` whose KV
    cache is *manually* sharded along ``axis`` on its sequence dim.

    With a posit ``kv_spec`` (``core.transprecision.KVStorage``) the impl
    speaks the packed protocol (``attn.packed_kv = True``): the wire/HBM
    payload is posit CODES + per-row scales sharded along the sequence
    axis — each shard decodes its slice locally right before the partial
    LSE reduction, so full-precision K/V never cross HBM or ICI and the
    sharded cache stays ``bits/16`` of the bf16 footprint.

    With ``paged=True`` (posit spec only) the impl speaks the *paged*
    protocol (``attn.paged_kv = True``): the pool's flat rows are sharded
    along ``axis`` — each shard owns a contiguous physical page range —
    while the page table and per-slot lengths ship replicated next to the
    codes + scales.  A shard gathers only the table entries that fall in
    its page range, masks the rest, and joins the same O(activation-row)
    LSE combine; collective volume stays independent of context length
    AND of how many pages are live.  Requires num_pages divisible by the
    ``axis`` size (pages never straddle shards)."""
    n_shard = mesh.shape[axis]
    if paged and kv_spec is not None and kv_spec.is_posit:
        from ..kernels import kv_cache as kv_kernels

        def attn_paged(q, k_codes, v_codes, seq_lens, *, k_scale, v_scale,
                       page_table, page_size=page_size, **_):
            r, nkv, _ = k_codes.shape
            b, _, nh, hd = q.shape
            grp = nh // nkv
            qg = q.reshape(b, 1, nkv, grp, hd) * (hd ** -0.5)
            lens = jnp.broadcast_to(jnp.asarray(seq_lens, jnp.int32), (b,))
            tbl = jnp.asarray(page_table, jnp.int32)

            def shard_fn(qs, kc, ks, vc, vs, tb, ln):
                np_local = kc.shape[0] // page_size
                start = jax.lax.axis_index(axis) * np_local
                loc = tb - start                       # local page ids
                own = (loc >= 0) & (loc < np_local)    # (B, Pmax)
                rows = (jnp.clip(loc, 0, np_local - 1)[:, :, None]
                        * page_size + jnp.arange(page_size)).reshape(b, -1)
                kf = kv_kernels.decode_kv_rows(
                    kc[rows], ks[rows][..., None], kv_spec.fmt,
                    kv_spec.packed)                    # (B, L, nkv, hd)
                vf = kv_kernels.decode_kv_rows(
                    vc[rows], vs[rows][..., None], kv_spec.fmt,
                    kv_spec.packed)
                s = jnp.einsum("bqkgh,bskh->bkgqs", qs,
                               kf).astype(jnp.float32)[..., 0, :]
                kpos = jnp.arange(rows.shape[1])
                valid = (jnp.repeat(own, page_size, axis=1)
                         & (kpos[None, :] < ln[:, None]))
                s = jnp.where(valid[:, None, None, :], s, NEG_INF)
                m = s.max(-1)
                p = jnp.exp(s - m[..., None])
                l = p.sum(-1)
                o = jnp.einsum("bkgs,bskh->bkgh", p.astype(vf.dtype),
                               vf).astype(jnp.float32)
                m_g = jax.lax.pmax(m, axis)
                corr = jnp.exp(m - m_g)
                num = jax.lax.psum(o * corr[..., None], axis)
                den = jax.lax.psum(l * corr, axis)
                return (num / jnp.maximum(den, 1e-30)[..., None]).astype(
                    q.dtype)

            out = jax.shard_map(
                shard_fn, mesh=mesh,
                in_specs=(P(), P(axis, None, None), P(axis, None),
                          P(axis, None, None), P(axis, None), P(), P()),
                out_specs=P(), axis_names={axis}, check_vma=False)(
                    qg, k_codes, k_scale, v_codes, v_scale, tbl, lens)
            return out.reshape(b, 1, nh, hd)

        attn_paged.paged_kv = True
        return attn_paged
    if kv_spec is not None and kv_spec.is_posit:
        from ..kernels import kv_cache as kv_kernels

        def attn_packed(q, k_codes, v_codes, cache_len, *, k_scale, v_scale,
                        **_):
            b, w, nkv, _ = k_codes.shape
            nh, hd = q.shape[2], q.shape[3]
            grp = nh // nkv
            qg = q.reshape(b, 1, nkv, grp, hd) * (hd ** -0.5)
            cache_len = jnp.asarray(cache_len)

            def shard_fn(qs, kc, ks, vc, vs, cl):
                wl = kc.shape[1]
                start = jax.lax.axis_index(axis) * wl
                kf = kv_kernels.decode_kv_rows(kc, ks[..., None],
                                               kv_spec.fmt, kv_spec.packed)
                vf = kv_kernels.decode_kv_rows(vc, vs[..., None],
                                               kv_spec.fmt, kv_spec.packed)
                o, l, m = _local_lse(qs, kf, vf, start, cl)
                m_g = jax.lax.pmax(m, axis)
                corr = jnp.exp(m - m_g)
                num = jax.lax.psum(o * corr[..., None], axis)
                den = jax.lax.psum(l * corr, axis)
                return (num / jnp.maximum(den, 1e-30)[..., None]).astype(
                    q.dtype)

            out = jax.shard_map(
                shard_fn, mesh=mesh,
                in_specs=(P(), P(None, axis, None, None),
                          P(None, axis, None),
                          P(None, axis, None, None),
                          P(None, axis, None), P()),
                out_specs=P(), axis_names={axis}, check_vma=False)(
                    qg, k_codes, k_scale, v_codes, v_scale, cache_len)
            return out.reshape(b, 1, nh, hd)

        attn_packed.packed_kv = True
        return attn_packed

    def attn(q, k_cache, v_cache, cache_len, **_):
        b, w, nkv, hd = k_cache.shape
        nh = q.shape[2]
        grp = nh // nkv
        qg = (q.reshape(b, 1, nkv, grp, hd) * (hd ** -0.5))
        cache_len = jnp.asarray(cache_len)

        def shard_fn(qs, ks, vs, cl):
            wl = ks.shape[1]
            start = jax.lax.axis_index(axis) * wl
            o, l, m = _local_lse(qs, ks, vs, start, cl)
            m_g = jax.lax.pmax(m, axis)
            corr = jnp.exp(m - m_g)
            num = jax.lax.psum(o * corr[..., None], axis)
            den = jax.lax.psum(l * corr, axis)
            return (num / jnp.maximum(den, 1e-30)[..., None]).astype(q.dtype)

        out = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(), P(None, axis, None, None),
                      P(None, axis, None, None), P()),
            out_specs=P(), axis_names={axis}, check_vma=False)(
                qg, k_cache, v_cache, cache_len)
        return out.reshape(b, 1, nh, hd)

    return attn


def make_distributed_decode_step(cfg, policy, mesh: Mesh, rules,
                                 axis: str = "model"):
    """decode_step with the LSE-combined distributed attention plugged in."""
    from ..core.transprecision import kv_storage
    attn_impl = distributed_decode_attention(
        mesh, axis, kv_spec=kv_storage(policy),
        paged=getattr(policy, "kv_layout", "ring") == "paged",
        page_size=getattr(policy, "kv_page_size", 16))

    def step(params, cache, tok):
        if cfg.family == "vlm":
            return serve_model.decode_step(params, cache, None, cfg, policy,
                                           embeds=tok, attn_impl=attn_impl)
        return serve_model.decode_step(params, cache, tok, cfg, policy,
                                       attn_impl=attn_impl)

    return step


def make_distributed_engine(cfg, policy, mesh: Mesh, max_batch: int,
                            max_len: int, axis: str = "model", *,
                            num_pages=None):
    """A three-stage :class:`~repro.serve.engine_api.TransprecisionEngine`
    whose ``generate`` runs the LSE-combined KV-sharded attention — the
    disaggregated API and the distributed decode path are the same code,
    differing only in the plugged ``attn_impl``."""
    from ..core.transprecision import kv_storage
    from .engine_api import TransprecisionEngine
    attn_impl = distributed_decode_attention(
        mesh, axis, kv_spec=kv_storage(policy),
        paged=getattr(policy, "kv_layout", "ring") == "paged",
        page_size=getattr(policy, "kv_page_size", 16))
    return TransprecisionEngine(cfg, policy, max_batch, max_len,
                                num_pages=num_pages, attn_impl=attn_impl)
