"""Serving launcher: batched requests through the three-stage engine.

``python -m repro.launch.serve --arch paper-edge --policy paper_edge_p8``
demonstrates the paper's deployment mode: an edge LM whose weights live in
posit P(8,2), decoded on load, serving a batch of concurrent requests with
continuous batching.  Underneath, serving is the disaggregated
``prefill -> insert -> generate`` API (``repro.serve.engine_api``):
prompts prefill in bucketed-length batches, insert into free decode
slots (scattered straight into pool pages on the paged layout), and one
jitted ``generate`` program ticks the whole batch.

``--async`` swaps the synchronous ``engine.serve`` loop for the threaded
orchestrator (``repro.serve.orchestrator``): a backpressured submission
queue with admission timeouts, Poisson arrivals at ``--rate`` req/s, and
host-side detokenize/streaming overlapped with device compute; it reports
TTFT and inter-token latency percentiles.  ``--overcommit`` (paged
layout) admits on current page demand instead of the worst case and
evicts/requeues the newest sequence if the pool runs dry.

Observability (``repro.obs``): ``--trace-out DIR`` runs the serving
under a profiler session and writes its trace (``.xplane.pb`` under
``DIR/plugins/profile/``; ``jax.profiler.ProfileData`` loads it): the
program's spans (engine stage dispatch, the decode tick's phases and
admission, orchestrator loop segments, the detokenizer thread, Python
collections) and, on a chip, the device's operations, on one clock; a
per-stage wall-clock breakdown table is printed at exit.
``--metrics-json metrics.json`` dumps the full metrics-registry
snapshot (counters, gauges, latency histograms with p50/p95/p99).

``--energy`` prints the modeled energy breakdown (``repro.obs.energy``):
each compiled engine stage costed by loop-aware HLO analysis, priced
with the paper's TALU per-MAC PDP row and a documented DRAM pJ/byte,
times the live per-stage call counters — joules total, uJ/token and the
per-stage precision mix.  ``--request-log requests.jsonl`` (async mode)
appends one JSON line per finished/rejected request with its full
lifecycle decomposition (queue wait / prefill / insert / decode), and
``--ttft-slo`` / ``--itl-slo`` (milliseconds) arm SLO-violation
counters in the registry.

Robustness (``repro.serve.faults`` / ``repro.serve.guard``):
``--fault-plan random:seed=3,n=6`` arms deterministic seed-driven fault
injection (stage errors/latency, pool-dry allocs, NaN-poisoned logits,
crashed workers under ``lethal=1``) together with the hardened
lifecycle — bounded exponential-backoff stage retries and the numeric
guard that quarantines non-finite logits and re-decodes the slot up a
precision-fallback ladder.  ``--deadline-s`` / ``--watchdog-s`` bound
per-request and scheduler-stall time in async mode, and ``--health``
prints the orchestrator's health snapshot (thread liveness, in-flight
depth, fault/guard counters) before exit.

The exit code is 0 only when every request reached its end without an
error (and, in async mode, the orchestrator stayed healthy and every
submitted stream finished).  ``--expect-errors`` waives the per-request
part for runs that arm faults on purpose.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from collections import Counter
from time import perf_counter

import jax
import numpy as np

from ..configs import get_config
from ..core.transprecision import PRESETS
from ..models import lm
from ..obs import format_breakdown, stage_breakdown
from ..serve.engine import Request, ServeConfig, ServingEngine
from .compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-edge")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--policy", default="paper_edge_p8",
                    choices=sorted(PRESETS))
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--kv-format", default=None,
                    choices=["f32", "bf16", "posit16", "posit8", "posit4"],
                    help="KV-cache storage override (None: policy default)")
    ap.add_argument("--kv-layout", default=None, choices=["ring", "paged"],
                    help="KV-cache layout override (None: policy default)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="paged layout: tokens per page")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="paged layout: pool size incl. trash page "
                         "(None: full reservation)")
    ap.add_argument("--overcommit", action="store_true",
                    help="paged layout: admit on current page demand and "
                         "evict-and-requeue the newest sequence when the "
                         "pool runs dry (stats['evictions'])")
    ap.add_argument("--async", dest="async_", action="store_true",
                    help="drive the threaded orchestrator (backpressured "
                         "queue, Poisson arrivals, per-token streaming) "
                         "instead of the synchronous serve loop; prints "
                         "TTFT/ITL percentiles")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="async: offered load in requests/s "
                         "(0 = submit back-to-back)")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="async: backpressure cap on requests in flight")
    ap.add_argument("--admission-timeout", type=float, default=60.0,
                    help="async: seconds submit may block on a full queue")
    ap.add_argument("--speculative", action="store_true",
                    help="self-speculative greedy decode: gamma posit8 "
                         "draft steps + one target-precision verify per "
                         "round (token-identical to baseline greedy)")
    ap.add_argument("--gamma", type=int, default=4,
                    help="speculative: draft tokens per round")
    ap.add_argument("--draft-kv-format", default="posit8",
                    choices=["f32", "bf16", "posit16", "posit8", "posit4"],
                    help="speculative: draft-pass KV storage format")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="write a profiler trace of the run to DIR "
                         "(the program's spans and, on a chip, the "
                         "device's ops, on one clock; load it with "
                         "jax.profiler.ProfileData, TensorBoard or "
                         "Perfetto) and print a per-stage wall-clock "
                         "breakdown")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the metrics-registry snapshot (counters, "
                         "gauges, latency histograms) on exit")
    ap.add_argument("--energy", action="store_true",
                    help="print the modeled energy breakdown on exit "
                         "(TALU pJ/MAC x HLO FLOPs + DRAM pJ/byte x HBM "
                         "bytes, per stage call)")
    ap.add_argument("--request-log", default=None, metavar="PATH",
                    help="append one JSON line per terminal request with "
                         "its lifecycle decomposition (queue wait / "
                         "prefill / insert / decode)")
    ap.add_argument("--ttft-slo", type=float, default=None, metavar="MS",
                    help="TTFT SLO threshold in ms; violations counted "
                         "in the metrics registry (orch.slo.*)")
    ap.add_argument("--itl-slo", type=float, default=None, metavar="MS",
                    help="inter-token latency SLO threshold in ms")
    ap.add_argument("--fault-plan", default=None, metavar="SPEC",
                    help="arm deterministic fault injection + the "
                         "hardened lifecycle (bounded stage retries; "
                         "numeric guard with precision-fallback re-decode "
                         "on the base engine).  SPEC is 'none', "
                         "'random:seed=3,n=6[,rounds=40][,slots=2]"
                         "[,lethal=1]' or a JSON fault-list file "
                         "(repro.serve.faults.FaultPlan.parse)")
    ap.add_argument("--deadline-s", type=float, default=None, metavar="S",
                    help="async: per-request deadline from submit; expiry "
                         "terminates the stream with error='deadline' and "
                         "reclaims its slot + pages")
    ap.add_argument("--watchdog-s", type=float, default=None, metavar="S",
                    help="async: fail all in-flight requests if the "
                         "scheduler makes no progress for this long")
    ap.add_argument("--health", action="store_true",
                    help="print the orchestrator health snapshot (JSON: "
                         "liveness, threads, in-flight depth, engine "
                         "occupancy, faults/guard counters) before exit; "
                         "sync mode prints the counter subset only")
    ap.add_argument("--expect-errors", action="store_true",
                    help="exit 0 even if requests end in a terminal error "
                         "(for runs whose fault plan causes them on "
                         "purpose); an unhealthy orchestrator still fails")
    args = ap.parse_args()

    if args.speculative and args.temperature > 0:
        ap.error("--speculative is greedy-only (temperature 0)")

    cfg = get_config(args.arch, smoke=not args.full)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    scfg = ServeConfig(max_batch=args.batch, max_len=args.max_len,
                       temperature=args.temperature,
                       kv_format=args.kv_format, kv_layout=args.kv_layout,
                       page_size=args.page_size, num_pages=args.num_pages,
                       page_overcommit=args.overcommit)
    faults = retry = None
    guard = False
    if args.fault_plan:
        from ..serve.faults import FaultPlan, RetryPolicy
        faults = FaultPlan.parse(args.fault_plan)
        retry = RetryPolicy()
        # the numeric guard is a base-engine decode policy (speculative
        # verify-round quarantine is a ROADMAP follow-on)
        guard = not args.speculative
    if args.speculative:
        from ..serve.speculative import SpeculativeEngine
        engine = SpeculativeEngine(cfg, params, scfg, policy=args.policy,
                                   gamma=args.gamma,
                                   draft_kv_format=args.draft_kv_format,
                                   faults=faults, retry=retry)
    else:
        engine = ServingEngine(cfg, params, scfg, policy=args.policy,
                               faults=faults, retry=retry, guard=guard)
    if args.trace_out:
        engine.tracer.enable()
    rng = np.random.default_rng(0)
    serve = _serve_async if args.async_ else _serve_sync
    with (jax.profiler.trace(args.trace_out) if args.trace_out
          else contextlib.nullcontext()):
        code = serve(engine, cfg, rng, args)
    if args.trace_out:
        print(f"profiler trace -> {args.trace_out}")
    return code


def _serve_sync(engine, cfg, rng, args):
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, rng.integers(4, 17)),
                    max_new=args.max_new)
            for i in range(args.requests)]
    t0 = perf_counter()
    stats = engine.serve(reqs)
    wall = perf_counter() - t0
    for r in reqs[:4]:
        print(f"req {r.uid}: {len(r.out_tokens)} tokens ->",
              r.out_tokens[:10], "...")
    if args.speculative:
        acc = stats["drafts_accepted"] / max(stats["drafts_proposed"], 1)
        spt = stats["decode_steps"] / max(stats["tokens"]
                                          - stats["prefills"], 1)
        print(f"speculative: gamma={args.gamma} acceptance={acc:.2f} "
              f"target steps/token={spt:.2f}")
    print("stats:", {k: (round(v, 2) if isinstance(v, float) else v)
                     for k, v in stats.items()})
    if args.request_log:    # sync path: dump the engine's own stamps
        with open(args.request_log, "a") as f:
            for r in reqs:
                f.write(json.dumps({"uid": r.uid, "error": r.error,
                                    "n_tokens": len(r.out_tokens),
                                    "lifecycle": r.timing}) + "\n")
        print(f"request log -> {args.request_log}")
    if args.health:    # sync path: no orchestrator, counters only
        c = engine.metrics.snapshot()["counters"]
        print("health:", json.dumps(
            {k: int(v) for k, v in sorted(c.items())
             if k.startswith(("faults.", "guard."))
             or k in ("stage.retries", "stage.retry_exhausted")}))
    _write_obs(engine, wall, args)
    errs = _count_errors(r.error for r in reqs)
    return _exit_code(errs, args)


def _count_errors(errors):
    return dict(Counter(e for e in errors if e is not None))


def _exit_code(errs, args, failures=()):
    """1 when the run failed: a failure of the serving machinery itself
    (``failures``), or a request error the caller did not expect."""
    failures = list(failures)
    if errs and not args.expect_errors:
        failures.append(f"requests ended in terminal errors: {errs}")
    for f in failures:
        print(f"FAILED: {f}", file=sys.stderr)
    return 1 if failures else 0


def _write_obs(engine, wall_s, args):
    """Dump trace / metrics files and print the stage breakdown."""
    if args.trace_out:
        print(format_breakdown(stage_breakdown(engine.tracer, wall_s)))
    if args.metrics_json:
        with open(args.metrics_json, "w") as f:
            json.dump(engine.metrics.snapshot(), f, indent=1)
        print(f"metrics snapshot -> {args.metrics_json}")
    if args.energy:
        from ..obs import EnergyAccountant, format_energy
        print(format_energy(EnergyAccountant(engine).breakdown()))


def _serve_async(engine, cfg, rng, args):
    import time

    from ..serve.orchestrator import (Orchestrator, OrchestratorConfig,
                                      StreamingRequest)
    ms = lambda v: v * 1e-3 if v is not None else None
    ocfg = OrchestratorConfig(max_queue=args.max_queue,
                              admission_timeout_s=args.admission_timeout,
                              detokenize=False,
                              deadline_s=args.deadline_s,
                              watchdog_s=args.watchdog_s,
                              ttft_slo_s=ms(args.ttft_slo),
                              itl_slo_s=ms(args.itl_slo),
                              request_log=args.request_log)
    sreqs = [StreamingRequest(
        rng.integers(0, cfg.vocab, rng.integers(4, 17)).tolist(),
        max_new=args.max_new) for _ in range(args.requests)]
    t0 = perf_counter()
    # no `with`: under a lethal fault plan a worker loop may die, and
    # __exit__ would re-raise its exception — we want to keep going and
    # report the health snapshot instead
    orch = Orchestrator(engine, ocfg)
    submitted, failures = [], []
    try:
        for s in sreqs:
            try:
                ok = orch.submit(s)
            except RuntimeError as e:   # orchestrator went unhealthy
                print(f"submit refused: {e}")
                failures.append(f"submit refused: {e}")
                break
            if not ok:
                print("request timed out in admission; dropping")
                failures.append("a request timed out in admission")
                continue
            submitted.append(s)
            if args.rate > 0:
                time.sleep(float(rng.exponential(1.0 / args.rate)))
        # containment guarantees every submitted request reaches a
        # terminal state, so these waits cannot hang; the timeout is a
        # belt-and-suspenders bound for the launcher itself
        unfinished = sum(not s.wait(timeout=300.0) for s in submitted)
        if unfinished:
            failures.append(f"{unfinished} streams never finished")
        if not orch.healthy:
            failures.append(f"orchestrator unhealthy: {orch.health()['error']}")
        if args.health:
            print("health:", json.dumps(orch.health()))
    finally:
        try:
            orch.close()
        except RuntimeError as e:       # leaked-thread detection
            print(f"close: {e}")
            failures.append(f"close: {e}")
    errs = _count_errors(s.error for s in submitted)
    if errs:
        print("terminal errors:", errs)
    wall = perf_counter() - t0
    for s in sreqs[:4]:
        print(f"stream: {len(s.out_tokens)} tokens ->",
              s.out_tokens[:10], "...")
    ttft = sorted(s.ttft_s for s in sreqs if s.ttft_s is not None)
    itl = sorted(g for s in sreqs for g in s.itl_s())
    pct = lambda xs, q: xs[min(int(q / 100 * len(xs)), len(xs) - 1)] * 1e3
    if ttft:
        print(f"TTFT p50/p99: {pct(ttft, 50):.1f}/{pct(ttft, 99):.1f} ms")
    if itl:
        print(f"ITL  p50/p99: {pct(itl, 50):.1f}/{pct(itl, 99):.1f} ms")
    print("orchestrator:", dict(orch.stats), "| engine:",
          {k: (round(v, 2) if isinstance(v, float) else v)
           for k, v in engine.stats.items()})
    if args.ttft_slo is not None or args.itl_slo is not None:
        c = engine.metrics.snapshot()["counters"]
        print("SLO:", {k: int(c.get(f"orch.slo.{k}", 0))
                       for k in ("ttft_violations", "ttft_total",
                                 "itl_violations", "itl_total")})
    if args.request_log:
        print(f"request log -> {args.request_log}")
    _write_obs(engine, wall, args)
    return _exit_code(errs, args, failures)


if __name__ == "__main__":
    sys.exit(main())
