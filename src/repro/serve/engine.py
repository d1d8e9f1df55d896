"""Batched serving engine: continuous batching over a TALU-style
transprecision model (posit-packed weights decoded on load).

This is the synchronous host-side *driver* over the disaggregated
three-stage engine API (``serve/engine_api.py``):

    prefill(params, tokens, lengths) -> Prefix
    insert(prefix, decode_state, slot) -> decode_state
    generate(params, decode_state)    -> (decode_state, logits)

Slot-based continuous batching: a fixed batch of B slots; finished
sequences free their slot and the next queued request is prefilled into it
while other slots keep decoding — the standard production pattern
(vLLM-style) reduced to its JAX-native core:

* ``generate`` is ONE jitted program for the whole batch, with TRUE
  per-slot positions (``cache["pos"]`` is a (B,) vector): heterogeneous
  prompt lengths batch correctly — each slot ropes, writes and masks at
  its own position, so greedy outputs match single-sequence decode
  exactly;
* prompts prefill in power-of-two *buckets* (right-padded, per-row true
  lengths — padding contributes exact zeros, so outputs are bit-identical
  to unpadded prefill) and ``add_requests`` admits several queued prompts
  through one prefill call; ``insert`` merges only the per-slot leaves
  (donated — no full-cache copy per admission);
* two KV layouts (``kv_layout``): ``ring`` reserves a dense max_len ring
  per slot; ``paged`` runs a shared posit page pool + per-sequence page
  tables (``serve/paged.py`` allocator, ``kernels/paged_kv.py`` device
  path), with prefill K/V rows scattered straight into pool pages.
  Admission control reserves each request's worst-case page demand
  (prompt + max_new), so mid-decode growth never exhausts the pool;
  with ``page_overcommit`` the reservation is waived and a dry pool
  instead *evicts* the newest sequence (recompute-on-readmit,
  ``stats["evictions"]``) — higher occupancy at the cost of recompute;
* admission scans the whole queue for the first admissible request, so
  one oversized/unplaceable head never starves slots later entries could
  fill (no head-of-line blocking);
* sampling: greedy or temperature (per-request); ``on_emit`` streams
  tokens to a host-side consumer (the async ``serve/orchestrator.py``)
  as they are produced.

For single-host examples this runs real tokens end-to-end; the multi-pod
decode path (KV-sharded + LSE combine) plugs in through the engine API's
``attn_impl`` hook (``serve/distributed.py``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.transprecision import BF16, TCPolicy, get_policy
from ..models import lm
from ..obs import MetricsRegistry, StatsView, Tracer
from .engine_api import TransprecisionEngine
from .faults import FaultInjector, FaultPlan, RetryPolicy
from .guard import GuardConfig, NumericGuard
from .paged import PageAllocator, SlotPages, pages_for

_KV_LEAF_NAMES = ("k", "v", "k_scale", "v_scale", "xk", "xv")
_POOL_LEAF_NAMES = ("k", "v", "k_scale", "v_scale")


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8
    max_len: int = 512
    temperature: float = 0.0     # 0 => greedy
    seed: int = 0
    eos_id: Optional[int] = None
    # KV-cache storage override (f32|bf16|posit16|posit8|posit4); None
    # keeps the policy's own kv_format / legacy packed_kv resolution.
    kv_format: Optional[str] = None
    # KV-cache layout override (ring|paged); None keeps the policy's.
    kv_layout: Optional[str] = None
    # paged layout: tokens per page (None keeps the policy's) and total
    # physical pages incl. the trash page (None = full reservation:
    # 1 + max_batch * ceil(max_len / page_size)).  Undersizing the pool
    # is how paging saves HBM: pages are *allocated* on demand as
    # sequences grow, but admission *reserves* each request's worst case
    # (prompt + max_new) in accounting, so decode-time growth can never
    # exhaust the pool — requests queue until reservations free up.
    page_size: Optional[int] = None
    num_pages: Optional[int] = None
    # waive the worst-case reservation and admit on current demand only;
    # if the pool then runs dry mid-decode the newest-admitted sequence
    # is evicted and requeued for recompute-on-readmit
    # (stats["evictions"]) instead of raising.
    page_overcommit: bool = False


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray           # (S,) int32
    max_new: int = 32
    # per-request sampling temperature; None inherits ServeConfig's.
    # 0 (or an inherited 0) means greedy — the speculative path keys its
    # greedy-only admission check off this same resolved value.
    temperature: Optional[float] = None
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    error: Optional[str] = None  # set when the request is rejected
    # lifecycle stamps (``time.perf_counter()``): submit, admit,
    # prefill_done, insert_done, first_token, finish.  Stamped with
    # ``setdefault`` so readmission after a page-pool eviction keeps the
    # request's ORIGINAL stamps — TTFT means first token ever streamed.
    timing: Dict[str, float] = dataclasses.field(
        default_factory=dict, repr=False)
    # recompute-on-readmit state for a page-pool eviction: the token
    # sequence (prompt + all-but-last emitted) the readmission prefills
    _resume: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)


class ServingEngine:
    def __init__(self, cfg: lm.ModelCfg, params, scfg: ServeConfig,
                 policy: TCPolicy = BF16, *, attn_impl=None,
                 tracer: Optional[Tracer] = None,
                 faults=None, retry: Optional[RetryPolicy] = None,
                 guard=None):
        self.cfg = cfg
        self.scfg = scfg
        self.policy = get_policy(policy)
        # observability: one registry per engine (the orchestrator and
        # the speculative draft engine share it); tracing defaults OFF —
        # span call sites stay in place at ~no cost (tests/test_obs.py
        # bounds the disabled overhead)
        self.tracer = tracer if tracer is not None else Tracer()
        self.metrics = MetricsRegistry()
        # chaos hardening (serve/faults.py, serve/guard.py) — all off by
        # default, leaving single `is not None` checks on the hot path:
        #   faults: a FaultPlan or FaultInjector of scheduled failures;
        #   retry:  bounded-backoff retry of transient stage failures;
        #   guard:  True or a GuardConfig arms the numeric quarantine +
        #           precision-fallback re-decode for non-finite logits.
        if faults is not None and isinstance(faults, FaultPlan):
            faults = FaultInjector(faults, metrics=self.metrics)
        self.faults: Optional[FaultInjector] = faults
        if self.faults is not None and self.faults.metrics is None:
            self.faults.metrics = self.metrics
        self.retry = retry
        self._guard_cfg = (guard if isinstance(guard, GuardConfig)
                           else (GuardConfig() if guard else None))
        overrides = {}
        if scfg.kv_format is not None:
            overrides["kv_format"] = scfg.kv_format
        if scfg.kv_layout is not None:
            overrides["kv_layout"] = scfg.kv_layout
        if scfg.page_size is not None:
            overrides["kv_page_size"] = scfg.page_size
        if overrides:
            tag = "+".join(f"{k[3:]}_{v}" for k, v in overrides.items())
            self.policy = dataclasses.replace(
                self.policy, name=f"{self.policy.name}+{tag}", **overrides)
        self.params = params
        b, L = scfg.max_batch, scfg.max_len
        self.paged = self.policy.kv_layout == "paged"

        if self.paged:
            ps = self.policy.kv_page_size
            self._pmax = pages_for(L, ps)
            self.num_pages = (scfg.num_pages if scfg.num_pages is not None
                              else 1 + b * self._pmax)
            self.allocator = PageAllocator(self.num_pages, ps,
                                           metrics=self.metrics,
                                           tracer=self.tracer,
                                           faults=self.faults)
            self.slot_pages = [SlotPages(ps) for _ in range(b)]
            # worst-case page reservations (admission control): pages a
            # slot may still grow into are committed but not yet allocated
            self._committed = 0
            self._slot_commit = [0] * b
            self._table = np.zeros((b, self._pmax), np.int32)
        else:
            self.allocator = None

        self.engine = TransprecisionEngine(
            cfg, self.policy, b, L,
            num_pages=self.num_pages if self.paged else None,
            attn_impl=attn_impl, tracer=self.tracer, metrics=self.metrics,
            faults=self.faults, retry=self.retry,
            # the guard's fallback re-decode re-reads the pre-generate
            # state, so a guarded engine must not donate it away
            donate=False if self._guard_cfg is not None else None)
        self.guard: Optional[NumericGuard] = (
            NumericGuard(self, self._guard_cfg)
            if self._guard_cfg is not None else None)
        self.cache = self.engine.init_decode_state()
        if self.paged:
            self.cache["page_table"] = jnp.asarray(self._table)
        self.slot_pos = np.zeros(b, np.int64)         # valid tokens per slot
        self.slot_req: List[Optional[Request]] = [None] * b
        self.last_tok = np.zeros((b, 1), np.int32)
        # admission order per slot: a dry pool evicts the newest sequence
        self._admit_seq = np.zeros(b, np.int64)
        self._admit_counter = 0
        self._evicted: List[Request] = []   # awaiting readmission
        # streaming hook: called as on_emit(req, [tokens]) from the decode
        # loop the moment tokens are appended (the orchestrator's detok /
        # per-token callbacks hang off this)
        self.on_emit: Optional[Callable[[Request, List[int]], None]] = None
        self._rng = np.random.default_rng(scfg.seed)
        # legacy ``stats`` surface, backed by the shared metrics registry
        # (every key is a registry counter/gauge named "engine.<key>")
        self.stats = StatsView(self.metrics, prefix="engine.")
        self.stats.bind_counters("prefills", "decode_steps", "tokens",
                                 "rejected", "evictions", "kv_pages_live",
                                 "kv_pages_table")
        self.stats.bind_gauges("peak_live_pages", "kv_cache_bytes")
        self.stats["kv_cache_bytes"] = self.kv_cache_bytes()

    # ---- cache footprint ----
    def _kv_bytes(self, pool_frac: float = 1.0, cache=None) -> int:
        """Sum KV-cache leaf bytes across any cache layout by leaf name
        (``k``/``v``/scales/cross-K/V at any depth — no layout-specific
        key assumptions).  ``pool_frac`` scales page-pool leaves (paged
        layout) by an allocated-page fraction; cross-K/V does not page.
        ``cache`` defaults to the engine's target cache (the speculative
        engine also passes its ring-layout draft cache, where
        ``pool_frac`` must stay 1.0)."""

        total = 0.0
        paged = self.paged and cache is None

        def visit(kp, leaf):
            nonlocal total
            name = str(getattr(kp[-1], "key", getattr(kp[-1], "idx", kp[-1])))
            if name not in _KV_LEAF_NAMES or not hasattr(leaf, "dtype"):
                return
            nbytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            if paged and name in _POOL_LEAF_NAMES:
                nbytes *= pool_frac
            total += nbytes

        jax.tree_util.tree_map_with_path(
            visit, dict(self.cache if cache is None else cache))
        return int(total)

    def kv_cache_bytes(self) -> int:
        """Reserved HBM footprint of the attention K/V state (codes +
        scales + cross-K/V), for every layout."""
        return self._kv_bytes()

    def kv_cache_live_bytes(self) -> int:
        """Footprint counting only allocated pages for the paged layout
        (== reserved for ring, which preallocates everything)."""
        if not self.paged:
            return self._kv_bytes()
        return self._kv_bytes(self.allocator.live_pages / self.num_pages)

    def kv_cache_peak_live_bytes(self) -> int:
        """High-water live-page footprint over the served run (== reserved
        for ring)."""
        if not self.paged:
            return self._kv_bytes()
        return self._kv_bytes(self.stats["peak_live_pages"] / self.num_pages)

    # ---- slot management ----
    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slot_req):
            if r is None:
                return i
        return None

    def free_slots(self) -> int:
        return sum(r is None for r in self.slot_req)

    def _admission_tokens(self, req: Request) -> np.ndarray:
        """Token sequence a (re)admission must prefill: the prompt — or,
        after a page-pool eviction, the prompt plus all-but-last emitted
        token (the last one is the readmitted slot's next decode input)."""
        if req._resume is not None:
            return req._resume
        return np.asarray(req.prompt)

    def _reserve(self, req: Request) -> Optional[Tuple[int, Any]]:
        """Host-side half of admission: claim a slot and (paged layout)
        the prompt's pool pages.  Returns (slot, prompt dst rows) or None
        when no slot / pages are free right now."""
        toks = self._admission_tokens(req)
        n = len(toks)
        if n >= self.scfg.max_len:
            raise ValueError(f"prompt length {n} >= max_len "
                             f"{self.scfg.max_len}; reject before admission")
        slot = self._free_slot()
        if slot is None:
            return None
        dst_rows = None
        if self.paged:
            ps = self.allocator.page_size
            if self.scfg.page_overcommit:
                worst = 0   # admit on current demand; dry pool evicts
            else:
                # admission control reserves the worst case this request
                # can grow to; allocation itself stays on-demand (live
                # bytes track actual tokens), and the reservation
                # invariant guarantees the growth allocs in step() can
                # never fail
                worst = self._worst_pages(req)
                if self._committed + worst > self.num_pages - 1:
                    return None
            pages = self.allocator.alloc(pages_for(n + 1, ps))
            if pages is None:       # non-overcommit: unreachable under
                return None         # the reservation invariant
            self._committed += worst
            self._slot_commit[slot] = worst
            self.slot_pages[slot] = sp = SlotPages(ps, pages)
            self._table[slot] = sp.table_row(self._pmax)
            self.cache["page_table"] = jnp.asarray(self._table)
            t = np.arange(n)
            dst_rows = np.asarray(pages, np.int64)[t // ps] * ps + t % ps
        self.slot_req[slot] = req
        self.slot_pos[slot] = n
        self._admit_counter += 1
        self._admit_seq[slot] = self._admit_counter
        return slot, dst_rows

    def _install(self, req: Request, slot: int, dst_rows, prefix,
                 row: int) -> None:
        """Device + bookkeeping half of admission: insert prefix row
        ``row`` into ``slot``, sample the first token, finish prompt-only
        requests."""
        n = int(self.slot_pos[slot])
        dst = None
        if dst_rows is not None:
            # pad to the prefix bucket width; padding rows land on the
            # trash row 0
            w = jax.tree_util.tree_leaves(
                prefix["cache"]["blocks"])[0].shape[2]
            dst = np.zeros(w, np.int64)
            dst[:n] = dst_rows
        self.cache = self.engine.insert(prefix, self.cache, slot, row,
                                        dst_rows=dst)
        req.timing.setdefault("insert_done", time.perf_counter())
        self.stats["prefills"] += 1
        if req._resume is not None:
            # recompute-on-readmit: the stream already holds every token
            # up to out_tokens[-1]; decode continues from it
            req._resume = None
            self.last_tok[slot, 0] = req.out_tokens[-1]
            return
        logits = np.asarray(prefix["logits"])[row]
        tok = int(self._sample(logits[None], [self._req_temp(req)])[0])
        self.last_tok[slot, 0] = tok
        self._emit(req, [tok])
        # prompt-only requests (max_new <= 1, or immediate EOS) finish at
        # admission — no decode tick, slot and pages free right away
        if (len(req.out_tokens) >= req.max_new
                or req.out_tokens[-1] == self.scfg.eos_id):
            req.done = True
            self._free_request_slot(slot)

    def add_request(self, req: Request) -> bool:
        """Prefill ``req`` into a free slot; False if no slot (or, paged,
        not enough free pages) — the request stays queued.  Prompts that
        can never fit (``serve`` rejects these up front) are a caller
        error here: raising beats silently corrupting the page
        accounting."""
        return all(self.add_requests([req]))

    def add_requests(self, reqs: Sequence[Request]) -> List[bool]:
        """Batched admission: reserve a slot per request, then run ONE
        bucketed prefill over every admitted prompt and insert per row.
        Returns per-request admission flags; reservation stops at the
        first request that doesn't fit (FIFO order is preserved)."""
        tr = self.tracer
        with tr.span("engine.admit"):
            toks = [self._admission_tokens(r) for r in reqs]
            admitted: List[Tuple[Request, int, Any, int]] = []
            ok = [False] * len(reqs)
            with tr.span("engine.pages"):
                for j, req in enumerate(reqs):
                    if not self.engine.bucketed and admitted:
                        break   # exact-length prefill: one prompt per call
                    r = self._reserve(req)
                    if r is None:
                        break   # no slot/pages: later entries wait
                    admitted.append((req, r[0], r[1], j))
                    ok[j] = True
            if not admitted:
                return ok
            now = time.perf_counter()
            for req, _, _, _ in admitted:
                sub = req.timing.setdefault("submit", now)
                if "admit" not in req.timing:   # first admission only: a
                    req.timing["admit"] = now   # readmit isn't a queue wait
                    if now > sub:
                        tr.record("queue.wait", sub, now, cat="queue")
            if self.engine.bucketed:
                bucket = self.engine.bucket_for(
                    max(len(toks[j]) for _, _, _, j in admitted))
                pad = np.zeros((len(admitted), bucket), np.int32)
                lens = np.zeros(len(admitted), np.int32)
                for row, (_, _, _, j) in enumerate(admitted):
                    pad[row, :len(toks[j])] = toks[j]
                    lens[row] = len(toks[j])
                prefix = self.engine.prefill(self.params, pad, lens)
            else:
                (_, _, _, j0) = admitted[0]
                prefix = self.engine.prefill(
                    self.params, np.asarray(toks[j0], np.int32)[None])
            done = time.perf_counter()
            for row, (req, slot, dst_rows, _) in enumerate(admitted):
                req.timing.setdefault("prefill_done", done)
                self._install(req, slot, dst_rows, prefix, row)
            return ok

    def _worst_pages(self, req: Request) -> int:
        """Worst-case page demand of ``req``: its admission tokens plus
        the remaining max_new budget, capped by max_len (the engine stops
        a slot before max_len) and floored at prompt + 1 — admission
        always allocates a page for the first decode append, so the
        reservation must cover it even when max_new is 0."""
        s = len(self._admission_tokens(req))
        remaining = max(req.max_new - len(req.out_tokens), 0)
        tokens = min(max(s + remaining, s + 1), self.scfg.max_len)
        return pages_for(tokens, self.allocator.page_size)

    def _free_request_slot(self, slot: int) -> None:
        """Release a finished request's slot (paged: return its pages to
        the allocator immediately and point the slot at the trash page)."""
        req = self.slot_req[slot]
        if req is not None and req.done:    # eviction frees too, but an
            req.timing.setdefault(          # evicted request isn't done
                "finish", time.perf_counter())
        self.slot_req[slot] = None
        self.slot_pos[slot] = 0
        if self.paged:
            self._committed -= self._slot_commit[slot]
            self._slot_commit[slot] = 0
            self.allocator.free(self.slot_pages[slot].pages)
            self.slot_pages[slot] = SlotPages(self.allocator.page_size)
            self._table[slot] = 0
            self.cache["page_table"] = jnp.asarray(self._table)
            # park the idle slot's write position on the trash page
            self.cache["pos"] = self.cache["pos"].at[slot].set(0)

    def _evict_newest(self) -> Optional[int]:
        """Pool-dry graceful degradation (``page_overcommit``): evict the
        most recently admitted active sequence — free its slot and pages,
        stash its progress for recompute-on-readmit, and requeue it.
        Returns the freed slot, or None with nothing left to evict."""
        cands = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not cands:
            return None
        slot = max(cands, key=lambda i: self._admit_seq[i])
        req = self.slot_req[slot]
        req._resume = np.concatenate(
            [np.asarray(req.prompt, np.int64),
             np.asarray(req.out_tokens[:-1], np.int64)])
        self._free_request_slot(slot)
        self._evicted.append(req)
        self.stats["evictions"] += 1
        return slot

    def _grow_pages(self, active: List[int], target) -> None:
        """Allocate pages so each active slot can write rows up to
        ``target(i) - 1`` this tick.  Under ``page_overcommit`` a dry
        pool evicts the newest sequence instead of raising (the evicted
        slot may be the growing one — its ``slot_req`` goes None and the
        caller refilters ``active``)."""
        grew = False
        for i in active:
            while self.slot_req[i] is not None:
                need = self.slot_pages[i].pages_needed(int(target(i)))
                if not need:
                    break
                pages = self.allocator.alloc(need)
                if pages is not None:
                    self.slot_pages[i].pages.extend(pages)
                    self._table[i] = self.slot_pages[i].table_row(self._pmax)
                    grew = True
                    break
                if not self.scfg.page_overcommit:
                    # the admission reservation makes this unreachable
                    raise RuntimeError(
                        "paged KV pool exhausted mid-decode — the "
                        "admission reservation invariant was violated "
                        "(pages allocated outside the engine?)")
                if self._evict_newest() is None:
                    raise RuntimeError(
                        "paged KV pool exhausted with no sequence left "
                        "to evict")
                grew = True
        if grew:
            self.cache["page_table"] = jnp.asarray(self._table)
        self.stats["peak_live_pages"] = max(
            self.stats["peak_live_pages"], self.allocator.live_pages)

    def _req_temp(self, req: Request) -> float:
        """Resolved sampling temperature for ``req`` (per-request override
        falls back to the engine-wide default)."""
        return (self.scfg.temperature if req.temperature is None
                else req.temperature)

    def _sample(self, logits: np.ndarray,
                temps: Optional[np.ndarray] = None) -> np.ndarray:
        """Sample next tokens row-wise.  ``temps`` is a per-row temperature
        vector (None = the engine-wide default for every row); rows at
        temperature <= 0 are greedy, the rest are softmax samples at their
        own temperature."""
        logits = logits[..., : self.cfg.vocab]
        greedy = logits.argmax(-1)
        if temps is None:
            temps = np.full(greedy.shape, self.scfg.temperature)
        temps = np.broadcast_to(np.asarray(temps, np.float32), greedy.shape)
        hot = temps > 0
        if not hot.any():
            return greedy
        t = np.where(hot, temps, 1.0)[..., None]
        p = jax.nn.softmax(jnp.asarray(logits) / t, -1)
        c = np.cumsum(np.asarray(p), -1)
        u = self._rng.random(c.shape[:-1] + (1,))
        sampled = (c < u).sum(-1)
        return np.where(hot, sampled, greedy)

    def _emit(self, req: Request, toks: List[int]) -> None:
        """Append newly decoded tokens to ``req`` and stream them through
        the ``on_emit`` hook."""
        if toks:
            req.timing.setdefault("first_token", time.perf_counter())
        req.out_tokens.extend(toks)
        self.stats["tokens"] += len(toks)
        if self.on_emit is not None:
            self.on_emit(req, toks)

    # ---- one decode tick for the whole batch ----
    def step(self):
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return
        tr = self.tracer
        with tr.span("engine.step"):
            with tr.span("engine.pages"):
                if self.paged:
                    # grow page lists so every active slot has a page for
                    # the token this tick writes at its own position
                    self._grow_pages(active, lambda i: self.slot_pos[i] + 1)
                    active = [i for i in active
                              if self.slot_req[i] is not None]
                    if not active:
                        return
                    # the live pages of the active slots, which the
                    # decode attention walks this tick, and the whole
                    # page table: their ratio is the share of a walk of
                    # every page that does work
                    ps = self.allocator.page_size
                    self.stats["kv_pages_live"] += sum(
                        pages_for(int(self.slot_pos[i]) + 1, ps) for i in active)
                    self.stats["kv_pages_table"] += (self.scfg.max_batch
                                                     * self._pmax)
                self.cache["tok"] = jnp.asarray(self.last_tok)
            # guard-armed engines retain the pre-generate state
            # (donate=False) so a quarantined slot can be re-decoded up
            # the precision ladder
            prev = self.cache if self.guard is not None else None
            self.cache, logits = self.engine.generate(self.params,
                                                      self.cache)
            hooked = self.faults is not None or self.guard is not None
            with tr.span("engine.logits"):
                logits = np.asarray(logits)
                if hooked:
                    logits = np.array(logits, copy=True)   # writable copy
            if hooked:
                poisons = {}
                if self.faults is not None:
                    poisons = self.faults.poison_round(
                        {i: self.slot_req[i].uid for i in active})
                    for i in poisons:
                        logits[i] = np.nan
                if self.guard is not None:
                    self.guard.check_round(prev, logits, active, poisons)
                    # ladder-exhausted requests terminated inside the
                    # guard: reclaim their slot + pages, drop them from
                    # this round
                    for i in active:
                        r = self.slot_req[i]
                        if r is not None and r.done:
                            self._free_request_slot(i)
                    active = [i for i in active
                              if self.slot_req[i] is not None]
            with tr.span("engine.sample"):
                temps = np.asarray([0.0 if r is None else self._req_temp(r)
                                    for r in self.slot_req], np.float32)
                toks = self._sample(logits, temps)
            self.stats["decode_steps"] += 1
            with tr.span("engine.emit"):
                for i in active:
                    req = self.slot_req[i]
                    tok = int(toks[i])
                    self.last_tok[i, 0] = tok
                    self.slot_pos[i] += 1
                    self._emit(req, [tok])
                    eos = self.scfg.eos_id
                    if (len(req.out_tokens) >= req.max_new
                            or (eos is not None and tok == eos)
                            or self.slot_pos[i] >= self.scfg.max_len - 1):
                        req.done = True
                        self._free_request_slot(i)

    def abort(self, req: Request, error: Optional[str] = None) -> None:
        """Terminally release ``req`` from outside the decode loop
        (deadline expiry, cancellation, crash containment): free its
        slot and pages if it is active, drop it from the eviction
        requeue, and mark it done.  Idempotent; must run on the thread
        driving the engine (the orchestrator's scheduler thread)."""
        req.done = True
        if error is not None and req.error is None:
            req.error = error
        for i, r in enumerate(self.slot_req):
            if r is req:
                self._free_request_slot(i)   # stamps finish (req.done)
                return
        if req in self._evicted:
            self._evicted.remove(req)
        req.timing.setdefault("finish", time.perf_counter())

    def _reject_reason(self, req: Request) -> Optional[str]:
        """Why ``req`` can NEVER be admitted (None = admissible once a
        slot/pages free up).  Subclasses add checks (the speculative
        engine needs chunk headroom and greedy sampling)."""
        n = len(self._admission_tokens(req))
        if n >= self.scfg.max_len:
            return (f"prompt length {n} >= "
                    f"max_len {self.scfg.max_len}")
        if self.paged:
            if self.scfg.page_overcommit:
                if pages_for(n + 1, self.allocator.page_size) \
                        > self.num_pages - 1:
                    return ("prompt alone needs more pages than the "
                            f"pool holds ({self.num_pages - 1} allocatable)")
            elif self._worst_pages(req) > self.num_pages - 1:
                return ("request worst case needs more pages than the "
                        f"pool holds ({self.num_pages - 1} allocatable)")
        return None

    def _admit(self, queue: List[Request]) -> None:
        """Admit every currently admissible queued request, scanning past
        blocked entries (no head-of-line blocking: an oversized or
        page-starved head must not starve slots later entries can fill).
        FIFO priority is kept — earlier entries get first pick."""
        i = 0
        while i < len(queue):
            req = queue[i]
            reject = self._reject_reason(req)
            if reject is not None:
                req.done = True
                req.error = reject
                now = time.perf_counter()
                req.timing.setdefault("submit", now)
                req.timing.setdefault("finish", now)
                self.stats["rejected"] += 1
                queue.pop(i)
                continue
            if self.add_request(req):
                queue.pop(i)
                continue
            i += 1

    def serve(self, requests: List[Request], max_ticks: int = 10_000
              ) -> Dict[str, Any]:
        """Run to completion with continuous batching.  Durations come
        from ``time.perf_counter()`` (monotonic, same clock as the
        tracer/orchestrator stamps) — never ``time.time()``."""
        queue = list(requests)
        t0 = time.perf_counter()
        for r in queue:                 # sync path: batch entry == submit
            r.timing.setdefault("submit", t0)
        ticks = 0
        while (queue or self._evicted
               or any(r is not None for r in self.slot_req)) \
                and ticks < max_ticks:
            if self._evicted:   # evicted sequences readmit first (oldest)
                queue[0:0] = self._evicted
                self._evicted.clear()
            with self.tracer.span("serve.admit"):
                self._admit(queue)
            with self.tracer.span("serve.step"):
                self.step()
            ticks += 1
        dt = time.perf_counter() - t0
        # live bytes at drain are ~0 by construction (every finished
        # request returns its pages); the peak is the meaningful figure
        return {"wall_s": dt, **self.stats,
                "kv_peak_live_bytes": self.kv_cache_peak_live_bytes(),
                "tok_per_s": self.stats["tokens"] / max(dt, 1e-9)}
