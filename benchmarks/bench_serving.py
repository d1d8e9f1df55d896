"""Serving latency percentiles under offered load (TTFT / ITL sweep).

Drives the async :class:`~repro.serve.orchestrator.Orchestrator` (the
three-stage prefill→insert→generate engine underneath) with Poisson
request arrivals at several offered loads, expressed as multiples of the
engine's measured single-stream service rate.  Per load point it reports
host-side latency percentiles — the numbers a serving deployment is
actually graded on:

  * TTFT  — submit-to-first-token, p50/p99 (prefill + queueing);
  * ITL   — inter-token latency within a stream, p50/p99 (decode round
    cadence; batched speculative commits would share one stamp);
  * achieved vs offered throughput (requests/s and tokens/s).

At offered load <= the service rate the queue stays short and p99 TTFT
tracks prefill latency; past saturation (the 2x point) queueing delay
dominates and p99 TTFT grows with the backlog — the sweep makes that
knee visible.  CPU-reference numbers on this container; the shape of the
curve, not the absolute latencies, is the artifact.

Rate accounting: the measurement window runs from the FIRST submit to
the LAST finish (both ``perf_counter`` stamps recorded by the
orchestrator), so achieved_rps can never exceed the offered rate beyond
the N/(N-1) edge correction — asserted per load point.  Each load point
also carries a per-stage wall-clock breakdown (dispatch per engine
stage, the engine's tick phases, orchestrator overhead) from the span
tracer (:mod:`repro.obs`); set ``REPRO_TRACE=1`` to run the sweep under
a profiler session, whose trace lands in ``results/BENCH_serving.trace/``.

Each load point also reports modeled **energy** (:mod:`repro.obs.energy`:
TALU pJ/MAC x HLO FLOPs + DRAM pJ/byte x HBM bytes, times the per-stage
call-counter deltas over the window) as joules/token and tok/J, plus SLO
violation counts against fixed TTFT/ITL thresholds; the cumulative
``energy_breakdown`` (per-stage precision mix included) lands in the
JSON, and every request's lifecycle decomposition is appended to
``results/BENCH_serving.requests.jsonl``.

Writes ``benchmarks/results/BENCH_serving.json``.

  PYTHONPATH=src python -m benchmarks.run serving
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import lm
from repro.obs import EnergyAccountant, Tracer, stage_breakdown
from repro.serve.engine import ServeConfig, ServingEngine
from repro.serve.orchestrator import (Orchestrator, OrchestratorConfig,
                                      StreamingRequest)

LOAD_FACTORS = (0.5, 1.0, 2.0)      # x the measured service rate
MAX_BATCH, MAX_LEN, MAX_NEW, N_REQ = 2, 64, 8, 8
KV_FORMAT = "posit8"
# fixed SLOs for the violation counters: loose enough that the 0.5x load
# point passes on CI CPUs, tight enough that saturation shows up
TTFT_SLO_S, ITL_SLO_S = 2.0, 1.0
RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")


def _prompts(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, int(rng.integers(4, 13))).tolist()
            for _ in range(N_REQ)]


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else None


def _slo_counters(eng):
    c = eng.metrics.snapshot()["counters"]
    return {k: int(c.get(f"orch.slo.{k}", 0))
            for k in ("ttft_total", "ttft_violations",
                      "itl_total", "itl_violations")}


# robustness accounting: injected faults, retried stage dispatches and
# guard precision-fallback re-decodes over the load window.  All zero on
# the default fault-free run — the point is that the counters (and their
# hooks) are present on the hot serving path at no measurable cost.
_FAULT_KEYS = ("faults.injected", "stage.retries", "stage.retry_exhausted",
               "guard.nonfinite_rows", "guard.quarantined",
               "guard.fallbacks", "orch.deadline_expired",
               "orch.cancelled", "orch.watchdog_fired")


def _fault_counters(eng):
    c = eng.metrics.snapshot()["counters"]
    return {k: int(c.get(k, 0)) for k in _FAULT_KEYS}


def _run_load(eng, prompts, rate_rps, rng, acct=None, request_log=None):
    """Submit N_REQ prompts with Poisson gaps at rate_rps; return metrics."""
    ev0 = eng.stats.get("evictions", 0)
    since = eng.tracer.self_times()
    slo0 = _slo_counters(eng)
    flt0 = _fault_counters(eng)
    calls0 = acct.calls_snapshot() if acct is not None else {}
    orch = Orchestrator(eng, OrchestratorConfig(max_queue=4 * N_REQ,
                                                detokenize=False,
                                                ttft_slo_s=TTFT_SLO_S,
                                                itl_slo_s=ITL_SLO_S,
                                                request_log=request_log))
    sreqs = [StreamingRequest(p, max_new=MAX_NEW) for p in prompts]
    gaps = rng.exponential(1.0 / rate_rps, size=len(sreqs))
    for sreq, gap in zip(sreqs, gaps):
        assert orch.submit(sreq, timeout=120.0)
        time.sleep(float(gap))
    for sreq in sreqs:
        assert sreq.wait(300.0), "stream did not finish"
    orch.close()
    # measurement window: first submit -> last finish (perf_counter stamps
    # recorded by the orchestrator).  The old form started the clock
    # before the first submit and stopped it after close(), which let
    # achieved_rps exceed the offered rate at low load (the window was
    # dominated by the submit gaps, not service time).
    first_submit = min(s.submit_t for s in sreqs)
    last_submit = max(s.submit_t for s in sreqs)
    wall = max(s.finish_t for s in sreqs) - first_submit
    achieved_rps = len(sreqs) / wall
    # sanity: over this window achieved <= offered up to the edge
    # correction — N requests span only N-1 submit gaps
    measured_offered = None
    if last_submit > first_submit:
        measured_offered = (len(sreqs) - 1) / (last_submit - first_submit)
        bound = measured_offered * len(sreqs) / (len(sreqs) - 1)
        assert achieved_rps <= bound * 1.001, \
            f"achieved {achieved_rps:.3f} rps exceeds offered bound " \
            f"{bound:.3f} rps — measurement window is wrong"
    ttft = [s.ttft_s for s in sreqs]
    itl = [g for s in sreqs for g in s.itl_s()]
    tokens = sum(len(s.out_tokens) for s in sreqs)
    bd = stage_breakdown(eng.tracer, wall, since=since)
    assert bd["attributed_frac"] >= 0.9, \
        f"stage breakdown covers only {bd['attributed_frac']:.0%} of wall"
    # the tracer's queue bucket must reproduce the per-request stamps:
    # both derive from the same submit/admit perf_counter pairs
    stamp_wait = sum(s.lifecycle_deltas().get("queue_wait_s", 0.0)
                     for s in sreqs)
    trace_wait = bd["queue"].get("queue.wait", {}).get("total_s", 0.0)
    assert abs(trace_wait - stamp_wait) <= 1e-6 + 1e-3 * stamp_wait, \
        f"queue bucket {trace_wait:.6f}s != stamp sum {stamp_wait:.6f}s"
    energy = None
    if acct is not None:
        delta = acct.calls_delta(acct.calls_snapshot(), calls0)
        e = acct.breakdown(calls=delta, tokens=tokens)
        energy = {"joules": e["joules_total"],
                  "joules_per_token": e["joules_per_token"],
                  "tok_per_joule": e["tok_per_joule"]}
    slo1 = _slo_counters(eng)
    return {"offered_rps": rate_rps,
            "measured_offered_rps": measured_offered,
            "achieved_rps": achieved_rps,
            "tok_per_s": tokens / wall,
            "ttft_ms": {"p50": _pct(ttft, 50) * 1e3,
                        "p99": _pct(ttft, 99) * 1e3},
            "itl_ms": {"p50": _pct(itl, 50) * 1e3,
                       "p99": _pct(itl, 99) * 1e3},
            "evictions": eng.stats.get("evictions", 0) - ev0,
            "energy": energy,
            "slo": {k: slo1[k] - slo0[k] for k in slo1},
            "faults": {k: v - flt0[k]
                       for k, v in _fault_counters(eng).items()},
            "stage_breakdown": bd}


def run():
    """The sweep; with ``REPRO_TRACE`` set, under a profiler session whose
    trace lands in ``results/BENCH_serving.trace/``."""
    if not os.environ.get("REPRO_TRACE"):
        return _sweep()
    path = os.path.join(RESULTS_DIR, "BENCH_serving.trace")
    with jax.profiler.trace(path):
        out = _sweep()
    out["trace_dir"] = os.path.basename(path)
    return out


def _sweep():
    cfg = get_config("paper-edge", smoke=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    scfg = ServeConfig(max_batch=MAX_BATCH, max_len=MAX_LEN,
                       kv_format=KV_FORMAT)
    eng = ServingEngine(cfg, params, scfg, tracer=Tracer(enabled=True))
    prompts = _prompts(cfg)
    acct = EnergyAccountant(eng)
    os.makedirs(RESULTS_DIR, exist_ok=True)
    reqlog = os.path.join(RESULTS_DIR, "BENCH_serving.requests.jsonl")
    open(reqlog, "w").close()   # truncate: one file per bench run

    # calibrate: back-to-back batch (compiles all prefill buckets + the
    # decode step, so the sweep below measures steady-state latency)
    rng = np.random.default_rng(1)
    warm = _run_load(eng, prompts, rate_rps=1e3, rng=rng)
    service_rps = warm["achieved_rps"]

    out = {"shape": {"max_batch": MAX_BATCH, "max_len": MAX_LEN,
                     "max_new": MAX_NEW, "requests": N_REQ,
                     "kv_format": KV_FORMAT},
           "slo": {"ttft_s": TTFT_SLO_S, "itl_s": ITL_SLO_S},
           "service_rps": service_rps, "loads": [],
           "request_log": os.path.basename(reqlog)}
    for f in LOAD_FACTORS:
        m = _run_load(eng, prompts, rate_rps=f * service_rps, rng=rng,
                      acct=acct, request_log=reqlog)
        m["load_factor"] = f
        out["loads"].append(m)
    # cumulative table (per-stage pJ, precision mix) over the whole run
    out["energy_breakdown"] = acct.breakdown()
    return out


def main(verbose=False):
    out = run()
    if verbose:
        print(f"[serving] service rate {out['service_rps']:.2f} req/s "
              f"({out['shape']['requests']} reqs, "
              f"max_new={out['shape']['max_new']})")
        for m in out["loads"]:
            bd = m["stage_breakdown"]
            en = m["energy"] or {}
            tpj = en.get("tok_per_joule")
            ej = f", {tpj:.0f} tok/J" if tpj else ""
            slo = m["slo"]
            print(f"  load {m['load_factor']:.1f}x: offered "
                  f"{m['offered_rps']:.2f} rps, achieved "
                  f"{m['achieved_rps']:.2f} rps | TTFT p50/p99 "
                  f"{m['ttft_ms']['p50']:.0f}/{m['ttft_ms']['p99']:.0f} ms"
                  f" | ITL p50/p99 {m['itl_ms']['p50']:.0f}/"
                  f"{m['itl_ms']['p99']:.0f} ms | "
                  f"{m['tok_per_s']:.1f} tok/s{ej} | "
                  f"SLO viol ttft {slo['ttft_violations']}/"
                  f"{slo['ttft_total']} itl {slo['itl_violations']}/"
                  f"{slo['itl_total']} | "
                  f"{bd['attributed_frac']:.0%} wall attributed")
        eb = out["energy_breakdown"]
        if eb["joules_per_token"] is not None:
            print(f"  energy (cumulative): "
                  f"{eb['joules_per_token'] * 1e6:.1f} uJ/token, "
                  f"{eb['tok_per_joule']:.0f} tok/J")
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "BENCH_serving.json"), "w") as f:
        json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    main(verbose=True)
