"""Self-speculative decoding vs baseline greedy: acceptance rate and
target-model decode steps per emitted token.

Serves the same request set through the baseline ``ServingEngine`` and
the ``SpeculativeEngine`` (posit8 draft policy) at several gamma values
and both KV layouts, reporting per cell:

  * acceptance rate — accepted drafts / proposed drafts (how often the
    posit8 pass agrees with the target-precision argmax, the paper's
    "low-bitwidth posit keeps accuracy close" claim doing real work);
  * target steps/token — verify passes per emitted decode token.  < 1.0
    means the expensive target-precision datapath runs LESS than once
    per token: the speculative win.  The draft steps are posit8-cheap
    and reported separately;
  * stream identity — speculative greedy output must equal baseline
    greedy output token for token (bit-exact verify + rollback);
  * tokens/s for both engines (CPU reference numbers on this container).

Acceptance target (ISSUE 3): identical streams and < 1.0 target
steps/token at gamma >= 2.

Every cell (and each layout's baseline) carries a ``stage_breakdown``
from the span tracer (:mod:`repro.obs`): per-stage dispatch seconds
(draft stages prefixed ``draft.``), host overhead, and the fraction of
wall attributed — the data behind ROADMAP direction 1's "why is
speculative wall-clock slower" question.  Set ``REPRO_TRACE=1`` to run
the sweep under a profiler session, whose trace (device time per stage
among it) lands in ``results/BENCH_speculative.trace/``.

Writes the machine-readable artifact ``benchmarks/results/
BENCH_speculative.json``.

  PYTHONPATH=src python -m benchmarks.run speculative
"""
from __future__ import annotations

import json
import os
from time import perf_counter

import jax
import numpy as np

from repro.configs import get_config
from repro.models import lm
from repro.obs import EnergyAccountant, Tracer, stage_breakdown
from repro.serve.engine import Request, ServeConfig, ServingEngine
from repro.serve.speculative import SpeculativeEngine

GAMMAS = (2, 4)
LAYOUTS = ("ring", "paged")
KV_FORMAT = "posit8"
MAX_BATCH, MAX_LEN, PAGE_SIZE, MAX_NEW, N_REQ = 2, 64, 8, 10, 4
RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")


def _requests(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(0, cfg.vocab,
                                               int(rng.integers(4, 13))),
                    max_new=MAX_NEW)
            for i in range(N_REQ)]


def _serve(engine_f, cfg, tracer):
    eng = engine_f()
    reqs = _requests(cfg)
    since = tracer.self_times()
    t0 = perf_counter()
    stats = eng.serve(reqs)
    wall = perf_counter() - t0
    stats["wall_s"] = wall
    stats["tok_per_s"] = stats["tokens"] / max(wall, 1e-9)
    stats["stage_breakdown"] = stage_breakdown(tracer, wall, since=since)
    # fresh engine per cell -> fresh registry: the cumulative breakdown
    # IS the cell's energy (per-stage pJ table + this cell's call counts)
    stats["energy_breakdown"] = EnergyAccountant(eng).breakdown()
    return [r.out_tokens for r in reqs], stats


def run():
    """The sweep; with ``REPRO_TRACE`` set, under a profiler session whose
    trace lands in ``results/BENCH_speculative.trace/``."""
    if not os.environ.get("REPRO_TRACE"):
        return _sweep()
    path = os.path.join(RESULTS_DIR, "BENCH_speculative.trace")
    with jax.profiler.trace(path):
        out = _sweep()
    out["trace_dir"] = os.path.basename(path)
    return out


def _sweep():
    cfg = get_config("paper-edge", smoke=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    # one tracer across the whole sweep: per-cell deltas via since=
    # snapshots
    tracer = Tracer(enabled=True)
    out = {"shape": {"max_batch": MAX_BATCH, "max_len": MAX_LEN,
                     "page_size": PAGE_SIZE, "max_new": MAX_NEW,
                     "requests": N_REQ, "kv_format": KV_FORMAT},
           "cells": {}, "baselines": {}}
    for layout in LAYOUTS:
        scfg = ServeConfig(max_batch=MAX_BATCH, max_len=MAX_LEN,
                           kv_format=KV_FORMAT, kv_layout=layout,
                           page_size=PAGE_SIZE)
        base_out, base_stats = _serve(
            lambda: ServingEngine(cfg, params, scfg, tracer=tracer), cfg,
            tracer)
        out["baselines"][layout] = {
            "tok_per_s": round(base_stats["tok_per_s"], 1),
            "stage_breakdown": base_stats["stage_breakdown"],
            "energy_breakdown": base_stats["energy_breakdown"]}
        target_step_pj = (base_stats["energy_breakdown"]["stages"]
                          .get("generate", {}).get("pj_per_call"))
        for gamma in GAMMAS:
            spec_out, s = _serve(
                lambda: SpeculativeEngine(cfg, params, scfg, gamma=gamma,
                                          tracer=tracer),
                cfg, tracer)
            decode_tokens = s["tokens"] - s["prefills"]
            cell = {
                "identical": spec_out == base_out,
                "acceptance_rate": round(
                    s["drafts_accepted"] / max(s["drafts_proposed"], 1), 4),
                "target_steps_per_token": round(
                    s["decode_steps"] / max(decode_tokens, 1), 4),
                "draft_steps_per_token": round(
                    s["draft_steps"] / max(decode_tokens, 1), 4),
                "spec_rounds": s["spec_rounds"],
                "tok_per_s": {"baseline": round(base_stats["tok_per_s"], 1),
                              "speculative": round(s["tok_per_s"], 1)},
                "stage_breakdown": s["stage_breakdown"],
                "energy_breakdown": s["energy_breakdown"],
            }
            # the speculative win in energy terms: one posit8-weight
            # draft step must cost less than one target-precision decode
            # step of the same layout's baseline engine (the spec engine
            # itself never runs a bare `generate`; verify replaces it)
            draft_step_pj = (s["energy_breakdown"]["stages"]
                            .get("draft.generate", {}).get("pj_per_call"))
            cell["energy"] = {
                "draft_step_pj": draft_step_pj,
                "target_step_pj": target_step_pj,
                "joules_per_token":
                    s["energy_breakdown"]["joules_per_token"],
                "draft_below_target": bool(
                    draft_step_pj is not None and target_step_pj is not None
                    and draft_step_pj < target_step_pj)}
            out["cells"][f"{layout}_gamma{gamma}"] = cell
    cells = out["cells"].values()
    out["all_identical"] = all(c["identical"] for c in cells)
    out["best_target_steps_per_token"] = min(
        c["target_steps_per_token"] for c in cells)
    out["draft_energy_below_target"] = all(
        c["energy"]["draft_below_target"] for c in cells)
    return out


def main(verbose=True):
    out = run()
    if verbose:
        sh = out["shape"]
        print(f"== Self-speculative decoding (batch={sh['max_batch']}, "
              f"max_new={sh['max_new']}, kv={sh['kv_format']}; "
              f"CPU reference) ==")
        print(f"{'cell':>14s} {'ident':>6s} {'accept':>7s} "
              f"{'tgt steps/tok':>14s} {'draft steps/tok':>16s} "
              f"{'draft/tgt uJ':>13s} {'uJ/tok':>8s}")
        for name, c in out["cells"].items():
            en = c["energy"]
            dt = (f"{en['draft_step_pj'] * 1e-6:.0f}/"
                  f"{en['target_step_pj'] * 1e-6:.0f}"
                  if en["draft_step_pj"] and en["target_step_pj"] else "-")
            jpt = en["joules_per_token"]
            print(f"{name:>14s} {str(c['identical']):>6s} "
                  f"{c['acceptance_rate']:>7.2f} "
                  f"{c['target_steps_per_token']:>14.2f} "
                  f"{c['draft_steps_per_token']:>16.2f} "
                  f"{dt:>13s} "
                  f"{jpt * 1e6 if jpt else 0:>8.1f}")
        print(f"  draft step below target step energy: "
              f"{out['draft_energy_below_target']}")
    os.makedirs(RESULTS_DIR, exist_ok=True)
    with open(os.path.join(RESULTS_DIR, "BENCH_speculative.json"), "w") as f:
        json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    main()
