"""Disaggregated serving-engine API: three jitted stages.

JetStream/maxtext-style split of the serving stack into separately
schedulable, separately jitted stages over one shared decode state:

    prefill(params, tokens, lengths) -> Prefix
    insert(prefix, decode_state, slot) -> decode_state
    generate(params, decode_state)    -> (decode_state, logits)

plus ``verify`` (the multi-token chunk pass speculative decoding drives)
and the rollback stages.  ``ServingEngine``/``SpeculativeEngine`` and the
async ``Orchestrator`` are thin host-side drivers over this API; the
distributed engine is the same API with a KV-sharded attention impl
plugged into the decode stages.

Design points:

* **Bucketed prefill.**  For decoder-only attention stacks, prompts are
  right-padded to a power-of-two bucket and prefilled at *bucket* width
  with per-row true lengths (``models.serve_model.prefill(true_len=...)``)
  — padded keys are causally masked to exact-zero attention contributions,
  so real rows' logits and K/V are bit-identical to an unpadded prefill.
  Mixed-length prompts share one prefill call and one compiled program per
  bucket instead of one per prompt length.
* **Prefix = bucket-width cache.**  ``prefill`` returns a ``Prefix`` pytree
  whose cache leaves are (B, bucket, ...) ring rows — never a full
  ``max_len`` cache.  On the paged layout the prompt K/V codes are
  codec-identical between the ring datapath and the pool, so ``insert``
  scatters the prefix rows straight into the slot's pool pages (the old
  ring-then-scatter intermediate max_len cache is retired).
* **One program per stage.**  ``generate`` is a single jitted program for
  the whole batch with true per-slot positions; ``insert`` is a donated
  per-slot merge touching only per-slot leaves; ``prefill`` compiles per
  (batch, bucket).  The decode state carries a ``"tok"`` leaf (B, 1) — the
  next input token per slot — which ``generate`` advances to its greedy
  argmax on-device; drivers overwrite it host-side for temperature-sampled
  rows.

Families outside the bucketed gate (sliding-window, recurrent/SSM, MoE,
audio/vlm) keep the legacy exact-length full-width prefill + whole-leaf
insert path, preserving their semantics unchanged.

Observability: every stage call is one ``stage.<prefix><stage>`` span
(cat ``engine``, :mod:`repro.obs`) around its dispatch, with no sync: it
lands in a profiler trace beside the stage's device programs whenever a
profiler session is active, so device time per stage is the device
trace's to give.  With the tracer enabled the dispatch time also feeds
the ``stage.<name>.dispatch_s`` histogram.  Always on: the
``stage.<name>.calls`` counters.  With tracing off a stage call is a
plain call after the profiler's ``is_enabled`` check.
"""
from __future__ import annotations

import dataclasses
from time import perf_counter, sleep
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.transprecision import TCPolicy, get_policy
from ..models.serve_model import (decode_step, init_cache, prefill,
                                  verify_step)
from ..obs import MetricsRegistry, Tracer
from ..obs.tracer import profiling

_POOL_LEAF_NAMES = ("k", "v", "k_scale", "v_scale")
_SCRUB_LEAVES = ("k", "v", "k_scale", "v_scale")
_MIN_BUCKET = 16

# A Prefix is a plain pytree: {"logits": (B, vocab_pad) — next-token
# logits per prompt, "cache": prefill cache (leaf rows at bucket width),
# "length": (B,) int32 true prompt lengths}.
Prefix = Dict[str, Any]


# ---------------------------------------------------------------------------
# Rollback stages (speculative decoding)
# ---------------------------------------------------------------------------

def rollback_ring_cache(cache, new_pos, window_end, scrub_from, t: int):
    """Rewind a ring-layout cache after a verify round: set ``pos`` to
    ``new_pos`` (B,) and scrub the speculatively written rows back to
    their init values (codes/floats 0, scales 1.0).

    Scatter form, O(B·t) rows touched: per slot only the *fixed-size*
    window of the last ``t`` rows written this round — rows
    ``[window_end - t, window_end)`` — is gathered, and rows at positions
    ``>= scrub_from`` are reset while the rest write their own value back
    (no-op).  Slots with nothing to scrub pass ``scrub_from ==
    window_end``.  All indices within a slot are distinct, so the scatter
    is order-independent.  No wraparound: row index == position, which
    ``verify_step`` guarantees by refusing sliding-window configs, and
    ``window_end <= max_len`` because a round never writes past the cap.
    """
    new = jnp.asarray(new_pos, jnp.int32)
    end = jnp.maximum(jnp.asarray(window_end, jnp.int32), t)
    frm = jnp.asarray(scrub_from, jnp.int32)
    off = jnp.arange(t, dtype=jnp.int32)
    rows = end[:, None] - t + off[None, :]          # (B, t), distinct/slot
    mask = rows >= frm[:, None]                     # True => reset to init

    def scrub_block(blk, stacked):
        # blocks leaves carry a leading period-stack axis (P, B, W, ...);
        # tail leaves are plain (B, W, ...)
        out = dict(blk)
        for name in _SCRUB_LEAVES:
            if name not in blk:
                continue
            leaf = blk[name]
            nb = leaf.shape[1 if stacked else 0]
            bi = jnp.arange(nb, dtype=jnp.int32)[:, None]
            init = jnp.asarray(1.0 if name.endswith("_scale") else 0,
                               leaf.dtype)
            if stacked:                              # (P, B, W, ...)
                cur = leaf[:, bi, rows]              # (P, B, t, ...)
                m = mask.reshape((1,) + mask.shape
                                 + (1,) * (leaf.ndim - 3))
                out[name] = leaf.at[:, bi, rows].set(jnp.where(m, init, cur))
            else:                                    # (B, W, ...)
                cur = leaf[bi, rows]                 # (B, t, ...)
                m = mask.reshape(mask.shape + (1,) * (leaf.ndim - 2))
                out[name] = leaf.at[bi, rows].set(jnp.where(m, init, cur))
        return out

    new_cache = dict(cache)
    new_cache["blocks"] = tuple(scrub_block(b, True) for b in cache["blocks"])
    if "tail" in cache:
        new_cache["tail"] = tuple(scrub_block(b, False)
                                  for b in cache["tail"])
    new_cache["pos"] = new
    return new_cache


def rollback_paged_cache(cache, new_pos, scrub_rows):
    """Rewind a paged-layout cache: set ``pos`` to ``new_pos`` (B,) and
    scrub the flat pool rows in ``scrub_rows`` (fixed-size (N,) i32,
    padded with trash row 0 — writes there are benign by construction)
    back to init values.  Page-table truncation and allocator frees are
    the engine's host-side half of the rollback."""
    rows = jnp.asarray(scrub_rows, jnp.int32)

    def scrub_block(blk, stacked):
        # blocks pool leaves carry a leading period-stack axis (P, R, ...);
        # tail leaves are plain (R, ...)
        out = dict(blk)
        for name in _SCRUB_LEAVES:
            if name not in blk:
                continue
            leaf = blk[name]
            init = jnp.asarray(1.0 if name.endswith("_scale") else 0,
                               leaf.dtype)
            out[name] = (leaf.at[:, rows].set(init) if stacked
                         else leaf.at[rows].set(init))
        return out

    new_cache = dict(cache)
    new_cache["blocks"] = tuple(scrub_block(b, True) for b in cache["blocks"])
    if "tail" in cache:
        new_cache["tail"] = tuple(scrub_block(b, False)
                                  for b in cache["tail"])
    new_cache["pos"] = jnp.asarray(new_pos, jnp.int32)
    return new_cache


def _abstract_args(args):
    """Arg pytree with arrays replaced by ``jax.ShapeDtypeStruct`` —
    static python scalars (jit ``static_argnums``) pass through.  The
    energy accountant (``repro.obs.energy``) re-lowers a stage from this
    spec to cost its compiled program without holding live buffers."""
    return jax.tree_util.tree_map(
        lambda x: (jax.ShapeDtypeStruct(x.shape, x.dtype)
                   if hasattr(x, "shape") and hasattr(x, "dtype") else x),
        args)


def _slot_update(dst, src, slot):
    """Write the single-row ``src`` into ``dst`` at batch index ``slot``.
    The batch axis is the first axis where the sizes differ; identical
    shapes mean max_batch == 1 (take src).  ``src`` may be narrower than
    ``dst`` on the row axis (bucket-width prefix rows land at [0, w))."""
    if dst.shape == src.shape:
        return src.astype(dst.dtype)
    ax = next(i for i, (a, b) in enumerate(zip(dst.shape, src.shape))
              if a != b)
    return jax.lax.dynamic_update_slice_in_dim(
        dst, src.astype(dst.dtype), slot, axis=ax)


class TransprecisionEngine:
    """The three-stage engine for one (model cfg, transprecision policy):

    * ``prefill(params, tokens, lengths)`` — run a (B, bucket) prompt
      batch, returning a :data:`Prefix`;
    * ``insert(prefix, state, slot, row, dst_rows)`` — merge prefix row
      ``row`` into batch slot ``slot`` of the decode state (paged layout:
      scatter its K/V rows to the ``dst_rows`` flat pool rows);
    * ``generate(params, state)`` — one decode tick for the whole batch;
      returns ``(state, logits)`` with ``state["tok"]`` advanced to the
      greedy next token per slot;
    * ``verify(params, state, chunk)`` — the (B, T) chunk pass for
      speculative verify rounds.

    The engine owns no request/queue state — drivers do.  ``attn_impl``
    plugs a custom decode-attention (e.g. the KV-sharded distributed
    path) into ``generate``."""

    def __init__(self, cfg, policy: TCPolicy, max_batch: int, max_len: int,
                 *, num_pages: Optional[int] = None, attn_impl=None,
                 donate: Optional[bool] = None,
                 tracer: Optional[Tracer] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 stage_prefix: str = "", faults=None, retry=None):
        self.cfg = cfg
        self.policy = get_policy(policy)
        # chaos hardening (both default None = zero-cost): ``faults`` is a
        # FaultInjector whose on_stage hook runs before every stage
        # dispatch; ``retry`` is a RetryPolicy absorbing *transient* stage
        # failures with bounded exponential backoff (serve/faults.py)
        self.faults = faults
        self.retry = retry
        # observability: a span per stage call, and dispatch-time
        # histograms while the tracer is enabled (the speculative draft
        # engine shares its driver's tracer/registry under a "draft."
        # stage prefix)
        self.tracer = tracer if tracer is not None else Tracer()
        self.metrics = metrics
        self.stage_prefix = stage_prefix
        self.max_batch, self.max_len = max_batch, max_len
        self.paged = getattr(self.policy, "kv_layout", "ring") == "paged"
        self.num_pages = num_pages
        self.attn_impl = attn_impl
        # bucketed (right-padded) prefill is exact only for decoder-only
        # attention stacks; other families keep exact-length prefill
        self.bucketed = (all(bt == "attn" for bt in cfg.block_types)
                         and not cfg.window
                         and cfg.family not in ("moe", "audio", "vlm"))
        if self.paged:
            # prompts prefill through the ring datapath at bucket width
            # (identical codec to the pool) and insert scatters the rows
            # into pool pages — no intermediate max_len ring cache
            self._prefill_policy = dataclasses.replace(
                self.policy, kv_layout="ring",
                name=self.policy.name + "+prefix")
        else:
            self._prefill_policy = self.policy
        # donation keeps per-stage state updates from copying the whole
        # batch cache; on by default on every backend, so the CPU tests
        # exercise the same donated buffers the chip does
        self._donate = True if donate is None else donate
        self._prefill_jits: Dict[Any, Any] = {}
        self._insert_jits: Dict[Any, Any] = {}
        self._verify_jits: Dict[int, Any] = {}
        self._rb_ring_jits: Dict[int, Any] = {}
        # always-on per-stage invocation counters ("stage.<name>.calls" in
        # the registry — the live multiplier of the energy model's static
        # pJ/invocation table) and the first-seen abstract arg spec per
        # stage, from which the energy accountant lowers + costs the
        # stage's compiled program.  Both are cheap on the hot path: one
        # dict hit + counter inc per stage call, spec capture only once.
        self._call_counters: Dict[str, Any] = {}
        self.stage_specs: Dict[str, Any] = {}
        self._generate_jit = jax.jit(
            self._generate_impl,
            donate_argnums=(1,) if self._donate else ())
        self._rb_paged = jax.jit(
            rollback_paged_cache,
            donate_argnums=(0,) if self._donate else ())

    # ---- observability ----
    def _staged(self, stage: str, fn, *args):
        """Run one engine stage inside its ``stage.<name>`` span.  The
        span covers the Python call (jit dispatch, and compilation on a
        cache miss); nothing waits for the device, so serving keeps
        XLA's async dispatch whether or not it is traced.  Untraced (tracer
        disabled, no profiler session) it is a plain call: no span
        object, no extra frame."""
        name = self.stage_prefix + stage
        if self.metrics is not None:
            ctr = self._call_counters.get(name)
            if ctr is None:
                ctr = self._call_counters[name] = self.metrics.counter(
                    f"stage.{name}.calls")
            ctr.inc()
        if name not in self.stage_specs:
            self.stage_specs[name] = (fn, _abstract_args(args))
        tr = self.tracer
        if not tr.enabled and not profiling():
            if self.faults is None and self.retry is None:
                return fn(*args)
            return self._invoke(name, fn, args)
        t0 = perf_counter()
        with tr.span("stage." + name, cat="engine"):
            out = self._invoke(name, fn, args)
        if self.metrics is not None and tr.enabled:
            self.metrics.histogram(f"stage.{name}.dispatch_s").observe(
                perf_counter() - t0)
        return out

    def _invoke(self, name, fn, args):
        """One stage call behind the fault-injection and retry hooks
        (plain call with neither armed).  Injection raises BEFORE the
        stage dispatches, so a failed attempt never consumes donated
        buffers; only exceptions flagged ``transient`` are retried, with
        bounded exponential backoff (``stage.retries`` /
        ``stage.<name>.retries`` counters; ``stage.retry_exhausted``
        when the budget runs out and the failure propagates)."""
        faults, retry = self.faults, self.retry
        if faults is None and retry is None:
            return fn(*args)
        tries = 0
        while True:
            try:
                if faults is not None:
                    faults.on_stage(name)
                return fn(*args)
            except Exception as e:
                transient = bool(getattr(e, "transient", False))
                tries += 1
                if retry is None or not transient \
                        or tries >= retry.max_attempts:
                    if transient and retry is not None \
                            and self.metrics is not None:
                        self.metrics.counter("stage.retry_exhausted").inc()
                    raise
                if self.metrics is not None:
                    self.metrics.counter("stage.retries").inc()
                    self.metrics.counter(f"stage.{name}.retries").inc()
                sleep(retry.delay(tries - 1))

    # ---- stage: decode-state construction ----
    def init_decode_state(self) -> Dict[str, Any]:
        """Empty decode state for ``max_batch`` slots: the KV cache pytree
        with per-slot ``pos`` plus the ``"tok"`` next-input leaf.  Paged
        engines with an explicit pool size get a zero page table (the
        driver owns it)."""
        kw = {"num_pages": self.num_pages} if self.paged else {}
        state = init_cache(self.cfg, self.max_batch, self.max_len,
                           policy=self.policy, **kw)
        state["pos"] = jnp.zeros((self.max_batch,), jnp.int32)
        state["tok"] = jnp.zeros((self.max_batch, 1), jnp.int32)
        return state

    # ---- stage: prefill ----
    def bucket_for(self, s: int) -> int:
        """Prefill width for an ``s``-token prompt: the smallest power-of-
        two bucket (>= 16, <= max_len) that holds it; non-bucketed
        families prefill at the exact length."""
        if not self.bucketed:
            return s
        b = _MIN_BUCKET
        while b < s:
            b <<= 1
        return min(b, self.max_len)

    def prefill(self, params, tokens, lengths=None) -> Prefix:
        """Run a prompt batch: ``tokens`` (B, S) int32, right-padded;
        ``lengths`` (B,) true prompt lengths (None = every row is exactly
        S tokens).  Returns a :data:`Prefix`.  Compiles once per (B, S)."""
        tokens = jnp.asarray(tokens, jnp.int32)
        b, s = tokens.shape
        if lengths is not None and not self.bucketed:
            raise ValueError(
                f"{self.cfg.name} prefills at exact length only "
                "(bucketed/padded prefill needs a decoder-only attention "
                "stack); pass lengths=None")
        key = (b, s, lengths is not None)
        fn = self._prefill_jits.get(key)
        if fn is None:
            # bucketed prefixes are bucket-width caches; legacy families
            # keep the full max_len prefix the whole-leaf insert expects
            plen = s if self.bucketed else self.max_len

            def impl(p, t, l):
                logits, cache = prefill(p, {"tokens": t}, self.cfg, plen,
                                        self._prefill_policy, true_len=l)
                return {"logits": logits, "cache": cache, "length": l}

            def impl_full(p, t):
                logits, cache = prefill(p, {"tokens": t}, self.cfg, plen,
                                        self._prefill_policy)
                return {"logits": logits, "cache": cache,
                        "length": jnp.full((t.shape[0],), s, jnp.int32)}

            fn = jax.jit(impl if lengths is not None else impl_full)
            self._prefill_jits[key] = fn
        if lengths is not None:
            return self._staged("prefill", fn, params, tokens,
                                jnp.asarray(lengths, jnp.int32))
        return self._staged("prefill", fn, params, tokens)

    # ---- stage: insert ----
    def insert(self, prefix: Prefix, state, slot, row=0, dst_rows=None):
        """Merge prefix row ``row`` into decode-state slot ``slot``.

        Ring layout: the prefix's bucket-width K/V rows land at rows
        [0, bucket) of the slot's ring via ``dynamic_update_slice``.
        Paged layout: they scatter directly to the ``dst_rows`` flat pool
        rows ((N,) i32, padded with trash row 0) — the prefix is never
        widened to max_len.  Donated; compiles once per (bucket, N)."""
        fn = self._insert_jits.get("fn")
        if fn is None:
            fn = jax.jit(self._insert_impl,
                         donate_argnums=(0,) if self._donate else (),
                         static_argnums=(5,))
            self._insert_jits["fn"] = fn
        dst = (None if dst_rows is None
               else jnp.asarray(dst_rows, jnp.int32))
        return self._staged(
            "insert", fn, state, prefix["cache"],
            jnp.asarray(prefix["length"], jnp.int32),
            jnp.asarray(slot, jnp.int32), jnp.asarray(row, jnp.int32),
            dst is None, dst)

    def _insert_impl(self, state, pcache, length, slot, row, ring, dst_rows):
        def merge_block(dstb, srcb, stacked):
            out = {}
            for name, d in dstb.items():
                src = srcb[name]
                # select prefix batch row `row`: (P, 1, w, ...) / (1, w, ...)
                s1 = jax.lax.dynamic_slice_in_dim(
                    src, row, 1, axis=1 if stacked else 0)
                if not ring and name in _POOL_LEAF_NAMES:
                    n = dst_rows.shape[0]
                    if stacked:        # (P, R, ...) <- (P, 1, w, ...)
                        out[name] = d.at[:, dst_rows].set(
                            s1[:, 0, :n].astype(d.dtype))
                    else:              # (R, ...) <- (1, w, ...)
                        out[name] = d.at[dst_rows].set(
                            s1[0, :n].astype(d.dtype))
                else:
                    out[name] = _slot_update(d, s1, slot)
            return out

        new_state = dict(state)
        new_state["pos"] = state["pos"].at[slot].set(
            length[row].astype(state["pos"].dtype))
        new_state["blocks"] = tuple(
            merge_block(d, s, True)
            for d, s in zip(state["blocks"], pcache["blocks"]))
        if "tail" in state:
            new_state["tail"] = tuple(
                merge_block(d, s, False)
                for d, s in zip(state["tail"], pcache["tail"]))
        # any other top-level per-slot state (e.g. audio "memory") merges
        # generically; page_table/tok are driver-owned, pos handled above
        for name, d in state.items():
            if name in ("pos", "blocks", "tail", "page_table", "tok"):
                continue
            if name in pcache:
                s1 = jax.lax.dynamic_slice_in_dim(pcache[name], row, 1, 0)
                new_state[name] = _slot_update(d, s1, slot)
        return new_state

    # ---- stage: generate ----
    def _generate_impl(self, params, state):
        tok = state["tok"]
        logits, new_state = decode_step(params, state, tok, self.cfg,
                                        self.policy,
                                        attn_impl=self.attn_impl)
        new_state["tok"] = jnp.argmax(
            logits[..., : self.cfg.vocab], axis=-1).astype(jnp.int32)[:, None]
        return new_state, logits

    def generate(self, params, state):
        """One decode tick for every slot: feeds ``state["tok"]``, writes
        each slot's K/V row at its own position, advances ``pos`` and
        ``tok`` (greedy argmax — drivers overwrite sampled rows).
        Returns ``(new_state, logits (B, vocab_pad))``.  Donates
        ``state``."""
        return self._staged("generate", self._generate_jit, params, state)

    # ---- stage: verify (speculative rounds) ----
    def verify(self, params, state, chunk):
        """Score a (B, T) draft chunk in one target-precision pass
        (``models.serve_model.verify_step``): token t of slot b is scored
        and its K/V row written at position ``pos[b] + t``.  Returns
        ``(new_state, logits (B, T, vocab_pad))``; ``state["tok"]`` is
        left for the driver to set after acceptance.  Compiles per T."""
        chunk = jnp.asarray(chunk, jnp.int32)
        t = chunk.shape[1]
        fn = self._verify_jits.get(t)
        if fn is None:
            def verify_impl(p, c, tk):
                logits, nc = verify_step(p, c, tk, self.cfg, self.policy)
                return nc, logits
            fn = jax.jit(verify_impl,
                         donate_argnums=(1,) if self._donate else ())
            self._verify_jits[t] = fn
        return self._staged("verify", fn, params, state, chunk)

    # ---- stage: rollback ----
    def rollback_ring(self, state, new_pos, window_end, scrub_from, t: int):
        """Jitted :func:`rollback_ring_cache` (compiled per window ``t``)."""
        fn = self._rb_ring_jits.get(t)
        if fn is None:
            fn = jax.jit(lambda c, n, e, f: rollback_ring_cache(c, n, e, f, t),
                         donate_argnums=(0,) if self._donate else ())
            self._rb_ring_jits[t] = fn
        return self._staged("rollback", fn, state,
                            np.asarray(new_pos, np.int32),
                            np.asarray(window_end, np.int32),
                            np.asarray(scrub_from, np.int32))

    def rollback_paged(self, state, new_pos, scrub_rows):
        """Jitted :func:`rollback_paged_cache`."""
        return self._staged("rollback", self._rb_paged, state,
                            np.asarray(new_pos, np.int32),
                            jnp.asarray(scrub_rows, jnp.int32))
