"""Chaos suite: seeded fault schedules against the hardened serving stack.

Three invariants, asserted under deterministic fault injection
(``repro.serve.faults``):

1. **no hangs** — every submitted request reaches a terminal state
   (tokens done, or a terminal ``error``) within a bounded wait, under
   benign AND lethal fault plans;
2. **no leaks** — the page allocator drains to zero live pages and
   passes ``assert_consistent()`` after every scenario, including
   deadline expiry, cancellation and crash containment;
3. **no blast radius** — streams whose requests were never faulted are
   token-identical to a fault-free run (retries, evictions and a
   neighbour's quarantine must not perturb them).

Plus targeted scenarios per failure mode: transient-retry identity,
persistent-error containment, numeric quarantine with precision-fallback
re-decode (``guard.fallbacks > 0``), ladder exhaustion, pool-dry
eviction, tokenize/detok/scheduler crash containment, the stuck-
scheduler watchdog and leaked-thread detection in ``close``.
"""
import json
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.transprecision import BF16, PRESETS
from repro.models import lm
from repro.serve import (Fault, FaultInjector, FaultPlan, GuardConfig,
                         InjectedFault, Orchestrator, OrchestratorConfig,
                         PageAllocator, Request, RetryPolicy, ServeConfig,
                         ServingEngine, StreamingRequest, fallback_ladder)

MAX_LEN = 64
POLICY = "paper_edge_p8"        # 2 real guard rungs (posit16 -> full)
RETRY = RetryPolicy(backoff_s=0.001, max_backoff_s=0.01)


@pytest.fixture(scope="module")
def smoke_model():
    cfg = get_config("paper-edge", smoke=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n).tolist()
               for n in (4, 11, 7, 5, 9, 6)]
    return cfg, params, prompts


def _engine(cfg, params, **kw):
    """Paged-overcommit engine (the layout every fault kind can hit:
    pool_dry needs overcommit's evict-don't-raise semantics)."""
    kw.setdefault("policy", POLICY)
    return ServingEngine(
        cfg, params,
        ServeConfig(max_batch=2, max_len=MAX_LEN, kv_layout="paged",
                    page_size=8, page_overcommit=True), **kw)


def _baseline(cfg, params, prompts, max_new):
    """Fault-free greedy token streams, one list per prompt."""
    eng = _engine(cfg, params)
    reqs = [Request(uid=i, prompt=np.asarray(p, np.int32), max_new=max_new)
            for i, p in enumerate(prompts)]
    eng.serve(reqs)
    assert all(r.done and r.error is None for r in reqs)
    return [list(r.out_tokens) for r in reqs]


def _assert_drained(eng):
    """Invariant 2: zero live pages + a consistent allocator."""
    assert eng.allocator.live_pages == 0
    eng.allocator.assert_consistent()


# ---------------------------------------------------------------------------
# the headline invariants, over seeded random schedules
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_seeded_chaos_invariants(smoke_model, seed):
    cfg, params, prompts = smoke_model
    max_new = 10
    ref = _baseline(cfg, params, prompts, max_new)

    plan = FaultPlan.random(seed, n=6, rounds=25, slots=2)
    eng = _engine(cfg, params, faults=plan, retry=RETRY, guard=True)
    sreqs = [StreamingRequest(p, max_new=max_new) for p in prompts]
    with Orchestrator(eng, OrchestratorConfig()) as orch:
        for s in sreqs:
            assert orch.submit(s, timeout=60.0)
        for s in sreqs:                      # invariant 1: no hangs
            assert s.wait(120.0), "request never reached a terminal state"
    _assert_drained(eng)                     # invariant 2: no leaks
    # benign plans: every fault kind is recoverable, so no errors at all
    assert all(s.error is None for s in sreqs), [s.error for s in sreqs]
    assert all(len(s.out_tokens) == max_new for s in sreqs)
    # invariant 3: un-faulted streams are token-identical to fault-free
    poisoned = eng.faults.uids_poisoned
    clean = [i for i, s in enumerate(sreqs)
             if s._req.uid not in poisoned]
    assert clean, "seeded plan poisoned every stream; weaken the plan"
    for i in clean:
        assert sreqs[i].out_tokens == ref[i], \
            f"un-faulted stream {i} diverged from the fault-free run"
    # poisoned streams recovered through the guard, not by luck
    if poisoned:
        c = eng.metrics.snapshot()["counters"]
        assert c["guard.fallbacks"] > 0


def test_seeded_lethal_chaos_terminates_everything(smoke_model):
    """Lethal plans (loop crashes, persistent errors): the only promised
    outcome is containment — every submitted stream terminal, no leaks,
    orchestrator flagged unhealthy if a loop died."""
    cfg, params, prompts = smoke_model
    plan = FaultPlan.random(7, n=8, rounds=20, slots=2, lethal=True)
    eng = _engine(cfg, params, faults=plan, retry=RETRY, guard=True)
    orch = Orchestrator(eng, OrchestratorConfig())
    submitted = []
    for s in [StreamingRequest(p, max_new=10) for p in prompts]:
        try:
            if orch.submit(s, timeout=60.0):
                submitted.append(s)
        except RuntimeError:
            break                            # containment beat us to it
    for s in submitted:
        assert s.wait(120.0), "request never reached a terminal state"
    try:
        orch.close()
    except RuntimeError:
        pass                                 # leaked-thread report is ok
    _assert_drained(eng)


# ---------------------------------------------------------------------------
# per-failure-mode scenarios
# ---------------------------------------------------------------------------

def test_transient_retry_token_identity(smoke_model):
    """Transient stage errors are absorbed by bounded retry and the
    output is bit-identical to the fault-free run."""
    cfg, params, prompts = smoke_model
    ref = _baseline(cfg, params, prompts[:4], 8)
    plan = FaultPlan((
        Fault("stage_error", stage="generate", at=1, count=2),
        Fault("stage_error", stage="prefill", at=1),
        Fault("stage_error", stage="insert", at=2),
    ))
    eng = _engine(cfg, params, faults=plan, retry=RETRY)
    reqs = [Request(uid=i, prompt=np.asarray(p, np.int32), max_new=8)
            for i, p in enumerate(prompts[:4])]
    eng.serve(reqs)
    assert [r.out_tokens for r in reqs] == ref
    c = eng.metrics.snapshot()["counters"]
    assert c["stage.retries"] >= 4 and c["faults.injected"] == 4
    _assert_drained(eng)


def test_persistent_stage_error_is_contained(smoke_model):
    """A non-transient stage failure exhausts nothing (retry only covers
    transient faults) and kills the scheduler loop; containment finishes
    every stream with an error and the engine drains clean."""
    cfg, params, prompts = smoke_model
    plan = FaultPlan((Fault("stage_error", stage="generate", at=2,
                            transient=False),))
    eng = _engine(cfg, params, faults=plan, retry=RETRY)
    orch = Orchestrator(eng, OrchestratorConfig())
    sreqs = [StreamingRequest(p, max_new=50) for p in prompts[:4]]
    submitted = [s for s in sreqs if orch.submit(s, timeout=60.0)]
    for s in submitted:
        assert s.wait(120.0)
    assert all(s.error for s in submitted)
    assert not orch.healthy
    assert isinstance(orch.worker_exc, InjectedFault)
    with pytest.raises(RuntimeError, match="unhealthy"):
        orch.submit(StreamingRequest(prompts[0]))
    orch.close()
    _assert_drained(eng)


def test_poison_quarantine_precision_fallback(smoke_model):
    """A NaN-poisoned slot is quarantined and re-decoded up the ladder:
    the stream completes without error, ``guard.fallbacks > 0``, and the
    un-poisoned neighbour stays token-identical to fault-free."""
    cfg, params, prompts = smoke_model
    ref = _baseline(cfg, params, prompts[:2], 10)
    plan = FaultPlan((Fault("poison_logits", at=3, slot=0,
                            fixed_by_level=2),))
    eng = _engine(cfg, params, faults=plan, retry=RETRY, guard=True)
    reqs = [Request(uid=i, prompt=np.asarray(p, np.int32), max_new=10)
            for i, p in enumerate(prompts[:2])]
    eng.serve(reqs)
    assert all(r.done and r.error is None for r in reqs)
    c = eng.metrics.snapshot()["counters"]
    assert c["guard.nonfinite_rows"] == 1
    assert c["guard.fallbacks"] == 2         # rung 1 still NaN, rung 2 fixes
    assert c["guard.exhausted"] == 0
    (poisoned_uid,) = eng.faults.uids_poisoned
    assert eng.guard.level(poisoned_uid) == 2
    clean = [r for r in reqs if r.uid != poisoned_uid]
    assert [r.out_tokens for r in clean] \
        == [ref[r.uid] for r in clean]       # zero blast radius
    _assert_drained(eng)


def test_guard_ladder_exhaustion_fails_one_request(smoke_model):
    """Non-finite logits that persist through the whole ladder terminate
    that request with an error; the batch neighbour is untouched."""
    cfg, params, prompts = smoke_model
    plan = FaultPlan((Fault("poison_logits", at=3, slot=0,
                            fixed_by_level=99),))
    eng = _engine(cfg, params, faults=plan, retry=RETRY, guard=True)
    reqs = [Request(uid=i, prompt=np.asarray(p, np.int32), max_new=10)
            for i, p in enumerate(prompts[:2])]
    eng.serve(reqs)
    (poisoned_uid,) = eng.faults.uids_poisoned
    bad = next(r for r in reqs if r.uid == poisoned_uid)
    good = next(r for r in reqs if r.uid != poisoned_uid)
    assert bad.done and "precision-fallback ladder" in bad.error
    assert good.done and good.error is None
    assert len(good.out_tokens) == 10
    assert eng.metrics.snapshot()["counters"]["guard.exhausted"] == 1
    _assert_drained(eng)


def test_pool_dry_fault_evicts_and_recovers(smoke_model):
    """An injected dry pool mid-growth evicts the newest sequence;
    recompute-on-readmit keeps every stream identical to fault-free."""
    cfg, params, prompts = smoke_model
    ref = _baseline(cfg, params, prompts[:4], 10)
    # alloc calls 0/1 are the two admissions (max_batch=2; queued
    # requests don't reach alloc while slots are full), so call 2 is the
    # first mid-decode growth alloc — the eviction path
    plan = FaultPlan((Fault("pool_dry", at=2, count=2),))
    eng = _engine(cfg, params, faults=plan, retry=RETRY)
    reqs = [Request(uid=i, prompt=np.asarray(p, np.int32), max_new=10)
            for i, p in enumerate(prompts[:4])]
    stats = eng.serve(reqs)
    assert stats["evictions"] >= 1
    assert [r.out_tokens for r in reqs] == ref
    _assert_drained(eng)


def test_deadline_expiry_reclaims_slot(smoke_model):
    cfg, params, prompts = smoke_model
    eng = _engine(cfg, params)
    orch = Orchestrator(eng, OrchestratorConfig(deadline_s=0.05))
    doomed = StreamingRequest(prompts[0], max_new=100_000)
    assert orch.submit(doomed)
    assert doomed.wait(60.0)
    assert doomed.error == "deadline"
    # the freed slot serves later requests normally (no deadline)
    ok = StreamingRequest(prompts[1], max_new=6, deadline_s=120.0)
    assert orch.submit(ok)
    assert ok.wait(60.0) and ok.error is None and len(ok.out_tokens) == 6
    assert orch.stats["deadline_expired"] == 1
    orch.close()
    _assert_drained(eng)


def test_cancel_mid_decode(smoke_model):
    cfg, params, prompts = smoke_model
    eng = _engine(cfg, params)
    orch = Orchestrator(eng, OrchestratorConfig())
    s = StreamingRequest(prompts[0], max_new=100_000)
    assert orch.submit(s)
    while not s.out_tokens:                   # genuinely mid-decode
        time.sleep(0.005)
    s.cancel()
    assert s.wait(60.0)
    assert s.error == "cancelled" and s.cancelled
    assert 0 < len(s.out_tokens) < 100_000
    lc = s.lifecycle()
    assert "submit" in lc and "finish" in lc and "first_token" in lc
    assert orch.stats["cancelled"] == 1
    orch.close()
    _assert_drained(eng)


def test_detok_crash_containment(smoke_model):
    cfg, params, prompts = smoke_model
    plan = FaultPlan((Fault("detok_crash", at=1),))
    eng = _engine(cfg, params, faults=plan)
    orch = Orchestrator(eng, OrchestratorConfig())
    sreqs = [StreamingRequest(p, max_new=30) for p in prompts[:4]]
    submitted = [s for s in sreqs if orch.submit(s, timeout=60.0)]
    for s in submitted:
        assert s.wait(120.0), "stream stranded behind a dead detokenizer"
    assert not orch.healthy
    h = orch.health()
    assert h["worker_exc"] and "detok" in h["error"]
    orch.close()
    _assert_drained(eng)


def test_tokenize_crash_containment(smoke_model):
    cfg, params, prompts = smoke_model
    plan = FaultPlan((Fault("tokenize_crash", at=1),))
    eng = _engine(cfg, params, faults=plan)
    orch = Orchestrator(eng, OrchestratorConfig())
    sreqs = [StreamingRequest(p, max_new=8) for p in prompts[:4]]
    submitted = [s for s in sreqs if orch.submit(s, timeout=60.0)]
    for s in submitted:
        assert s.wait(120.0), "stream stranded after a tokenize crash"
    # the crash victim itself carries the tokenize error, the rest the
    # containment error — nobody hangs
    assert any("tokenize failed" in (s.error or "") for s in submitted)
    assert not orch.healthy
    orch.close()
    _assert_drained(eng)


def test_sched_crash_health_and_exit_propagation(smoke_model):
    cfg, params, prompts = smoke_model
    plan = FaultPlan((Fault("sched_crash", at=3),))
    eng = _engine(cfg, params, faults=plan)
    with pytest.raises(RuntimeError, match="worker crashed") as ei:
        with Orchestrator(eng, OrchestratorConfig()) as orch:
            sreqs = [StreamingRequest(p, max_new=50) for p in prompts[:4]]
            submitted = []
            for s in sreqs:
                try:
                    if orch.submit(s, timeout=60.0):
                        submitted.append(s)
                except RuntimeError:
                    break
            for s in submitted:
                assert s.wait(120.0)
            orch._sched.join(30.0)          # let the dying loop finish
            h = orch.health()
            assert not h["healthy"] and h["in_flight"] == 0
            assert h["threads"]["orch-scheduler"] is False
            assert set(h["threads"]) == {"orch-scheduler", "orch-detok"}
            assert h["engine"]["live_pages"] == 0
    assert isinstance(ei.value.__cause__, InjectedFault)
    _assert_drained(eng)


def test_watchdog_fails_stuck_scheduler(smoke_model):
    """A 2s injected straggler against a 0.2s watchdog: in-flight
    requests fail fast instead of hanging for the stage duration."""
    cfg, params, prompts = smoke_model
    plan = FaultPlan((Fault("stage_delay", stage="generate", at=2,
                            delay_s=2.0),))
    eng = _engine(cfg, params, faults=plan)
    orch = Orchestrator(eng, OrchestratorConfig(watchdog_s=0.2))
    s = StreamingRequest(prompts[0], max_new=300)
    assert orch.submit(s)
    t0 = time.perf_counter()
    assert s.wait(60.0)
    assert time.perf_counter() - t0 < 1.9    # failed before the stall ended
    assert "watchdog" in s.error
    assert not orch.healthy
    assert orch.stats["watchdog_fired"] == 1
    orch.close()                             # straggler finishes inside 60s
    _assert_drained(eng)


def test_close_raises_on_leaked_threads(smoke_model):
    cfg, params, prompts = smoke_model
    plan = FaultPlan((Fault("stage_delay", stage="generate", at=2,
                            delay_s=3.0),))
    eng = _engine(cfg, params, faults=plan)
    orch = Orchestrator(eng, OrchestratorConfig())
    s = StreamingRequest(prompts[0], max_new=300)
    assert orch.submit(s)
    while not s.out_tokens:
        time.sleep(0.005)
    with pytest.raises(RuntimeError, match="leaked threads"):
        orch.close(timeout=0.2)
    # drain the straggler so it cannot bleed into other tests
    orch._sched.join(30.0)
    orch._detok.join(30.0)
    assert not orch._sched.is_alive() and not orch._detok.is_alive()


# ---------------------------------------------------------------------------
# units: plan parsing, allocator checks, ladder derivation
# ---------------------------------------------------------------------------

def test_fault_plan_parse_and_determinism(tmp_path):
    assert FaultPlan.parse("none").faults == ()
    p1 = FaultPlan.parse("random:seed=3,n=5,rounds=10,slots=2")
    p2 = FaultPlan.parse("random:seed=3,n=5,rounds=10,slots=2")
    assert p1 == p2 and len(p1.faults) == 5 and p1.seed == 3
    assert p1 != FaultPlan.parse("random:seed=4,n=5,rounds=10,slots=2")
    lethal = FaultPlan.random(0, n=40, lethal=True)
    kinds = {f.kind for f in lethal.faults}
    assert kinds & {"sched_crash", "detok_crash", "tokenize_crash"}
    benign = FaultPlan.random(0, n=40)
    assert all(f.transient for f in benign.faults
               if f.kind == "stage_error")
    path = tmp_path / "plan.json"
    path.write_text(json.dumps([
        {"kind": "stage_error", "stage": "generate", "at": 1},
        {"kind": "poison_logits", "slot": 1, "fixed_by_level": 2},
    ]))
    plan = FaultPlan.parse(str(path))
    assert plan.faults[0].stage == "generate"
    assert plan.faults[1].fixed_by_level == 2
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault("meteor_strike")
    with pytest.raises(ValueError, match="stage site"):
        Fault("stage_error")


def test_injected_fork_failure_leaves_allocator_consistent():
    alloc = PageAllocator(8, 4, faults=FaultInjector(
        FaultPlan((Fault("fork_fail", at=1),))))
    pages = alloc.alloc(3)
    forked = alloc.fork(pages)               # call 0: fine
    with pytest.raises(InjectedFault):
        alloc.fork(pages)                    # call 1: injected failure
    # the failed fork mutated nothing: refcounts still cover exactly the
    # two owners, and a full free drains the pool
    alloc.assert_consistent()
    assert all(alloc.ref_count(p) == 2 for p in pages)
    alloc.free(forked)
    alloc.free(pages)
    assert alloc.live_pages == 0
    alloc.assert_consistent()


def test_assert_consistent_catches_corruption():
    alloc = PageAllocator(6, 4)
    pages = alloc.alloc(2)
    alloc.assert_consistent()                # healthy state passes
    alloc._refs[pages[0]] = 0                # simulate a lost reference
    with pytest.raises(AssertionError, match="mismatch"):
        alloc.assert_consistent()
    alloc._refs[pages[0]] = 1
    alloc._free.append(alloc._free[-1])      # simulate a double free
    with pytest.raises(AssertionError, match="duplicates"):
        alloc.assert_consistent()


def test_fallback_ladder_shapes():
    ladder = fallback_ladder(PRESETS["paper_edge_p8"])
    assert len(ladder) == 2                  # posit16 rung, then full
    assert ladder[0].attn_weights == "posit16_2"
    assert ladder[1].attn_weights is None
    # KV settings never move — every rung reads the same decode state
    for rung in ladder:
        assert rung.kv_format == PRESETS["paper_edge_p8"].kv_format
        assert rung.kv_layout == PRESETS["paper_edge_p8"].kv_layout
    (retry_rung,) = fallback_ladder(BF16)    # full precision: one retry
    assert retry_rung.attn_weights is None
    assert "guard_retry" in retry_rung.name


@pytest.mark.parametrize("case,extra,want_rc", [
    ("deadline", [], 1),
    ("deadline", ["--expect-errors"], 0),
    ("crash", ["--expect-errors"], 1),
])
def test_launcher_exit_code(monkeypatch, tmp_path, capsys, case, extra,
                            want_rc):
    """``launch/serve.py --async`` exits 1 when a request ends in an error
    it was not told to expect, and when the orchestrator goes unhealthy
    even if it was (a persistent prefill fault crashes the scheduler)."""
    from repro.launch import serve
    if case == "deadline":                # healthy; every request expires
        args = ["--deadline-s", "0.001"]
    else:
        plan = tmp_path / "plan.json"
        plan.write_text(json.dumps([{"kind": "stage_error",
                                     "stage": "prefill", "at": 0,
                                     "transient": False}]))
        args = ["--fault-plan", str(plan)]
    # the launcher's persistent compile cache stays off under the tests
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    monkeypatch.setattr("sys.argv", [
        "serve", "--async", "--requests", "3", "--max-new", "3",
        "--batch", "2", "--max-len", str(MAX_LEN), *args, *extra])
    assert serve.main() == want_rc
    err = capsys.readouterr().err
    assert ("FAILED" in err) == bool(want_rc)
