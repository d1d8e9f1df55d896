"""Serve a small LM with batched requests under runtime-switchable
transprecision — the paper's deployment scenario (§IV-D): "if an
application requires FP/INT vector computation, then the design can be
switched ... without any performance overhead".

Serves the same request set under three TC policies (posit8 / int8 /
bf16), switching policy BETWEEN batches at runtime — each policy is just a
different jit specialization, the software analogue of the posit_en /
bitwidth control lines.  Then: KV-cache transprecision (PR 1), the paged
KV layout (PR 2), and self-speculative decoding (PR 3: posit8 draft +
target-precision verify + KV rollback, switching precision WITHIN a
decoding round).

  PYTHONPATH=src python examples/serve_transprecision.py
"""
import jax
import numpy as np

from repro.configs import get_config
from repro.core.transprecision import get_policy
from repro.models import lm
from repro.serve.engine import Request, ServeConfig, ServingEngine


def main():
    cfg = get_config("paper-edge", smoke=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, int(rng.integers(4, 12)))
               for _ in range(6)]

    outputs = {}
    for policy in ("paper_edge_p8", "int8_w", "bf16"):
        engine = ServingEngine(cfg, params,
                               ServeConfig(max_batch=3, max_len=96),
                               policy=get_policy(policy))
        reqs = [Request(uid=i, prompt=p, max_new=12)
                for i, p in enumerate(prompts)]
        stats = engine.serve(reqs)
        outputs[policy] = [r.out_tokens for r in reqs]
        print(f"policy={policy:14s} tokens/s={stats['tok_per_s']:8.1f} "
              f"decode_steps={stats['decode_steps']}")

    # posit8 weights change logits but the engine stays functional and the
    # higher-precision policies agree with each other more than with posit8
    agree_bf16_int8 = np.mean([a == b for a, b in
                               zip(outputs["bf16"], outputs["int8_w"])])
    print(f"\ngreedy-output agreement bf16 vs int8: {agree_bf16_int8:.2f}")
    print("runtime policy switching: OK (three jit specializations, "
          "no recompilation of unrelated variants)")

    # --- posit-packed KV cache (decode-on-read, PR 1) ------------------
    # Same bf16 weights, but the KV ring holds posit codes + per-row pow2
    # scales; posit16 reproduces the f32-cache greedy outputs at ~half the
    # cache footprint, posit8 at a quarter.
    print("\nKV-cache transprecision (bf16 weights, packed K/V ring):")
    kv_out = {}
    for kvf in ("f32", "posit16", "posit8"):
        engine = ServingEngine(cfg, params,
                               ServeConfig(max_batch=3, max_len=96,
                                           kv_format=kvf),
                               policy=get_policy("bf16"))
        reqs = [Request(uid=i, prompt=p, max_new=12)
                for i, p in enumerate(prompts)]
        stats = engine.serve(reqs)
        kv_out[kvf] = [r.out_tokens for r in reqs]
        print(f"  kv_format={kvf:8s} cache={stats['kv_cache_bytes']:7d} B "
              f"tokens/s={stats['tok_per_s']:8.1f}")
    match16 = np.mean([a == b for a, b in
                       zip(kv_out["posit16"], kv_out["f32"])])
    print(f"  greedy agreement posit16-KV vs f32-KV: {match16:.2f}")

    # --- paged KV cache (PR 2): page pool + per-sequence tables --------
    # Same posit codes, but slots stop reserving max_len rings: pages are
    # allocated as sequences grow and returned the moment they finish, so
    # HBM tracks live tokens.  Greedy outputs are bit-identical to the
    # ring layout (true per-slot positions in both).
    print("\nPaged KV cache (posit8 codes, page_size=8):")
    for layout in ("ring", "paged"):
        engine = ServingEngine(cfg, params,
                               ServeConfig(max_batch=3, max_len=96,
                                           kv_format="posit8",
                                           kv_layout=layout, page_size=8),
                               policy=get_policy("bf16"))
        reqs = [Request(uid=i, prompt=p, max_new=12)
                for i, p in enumerate(prompts)]
        stats = engine.serve(reqs)
        kv_out[layout] = [r.out_tokens for r in reqs]
        print(f"  layout={layout:6s} reserved={stats['kv_cache_bytes']:7d} B"
              f" peak_live={stats['kv_peak_live_bytes']:7d} B "
              f"tokens/s={stats['tok_per_s']:8.1f}")
    match = np.mean([a == b for a, b in zip(kv_out["paged"],
                                            kv_out["ring"])])
    print(f"  greedy agreement paged vs ring: {match:.2f} "
          "(exact by construction)")

    # --- self-speculative decoding (PR 3) ------------------------------
    # The TALU story end to end: gamma draft tokens per round under a
    # derived posit8 policy (posit8 weight compute + posit8 KV ring),
    # then ONE full-precision verify pass scores all gamma+1 positions;
    # accepted tokens commit, the first rejection rolls the KV cache
    # back (ring rewind / paged page-free).  Greedy output is
    # token-identical to the baseline engine — the draft precision only
    # sets the ACCEPTANCE RATE, i.e. how many target-model steps each
    # token costs.
    from repro.serve.speculative import SpeculativeEngine
    print("\nSelf-speculative decode (draft=posit8 weights+KV, "
          "target=f32 KV):")
    base = ServingEngine(cfg, params,
                         ServeConfig(max_batch=3, max_len=96,
                                     kv_format="f32"),
                         policy=get_policy("bf16"))
    reqs = [Request(uid=i, prompt=p, max_new=12)
            for i, p in enumerate(prompts)]
    base.serve(reqs)
    base_out = [r.out_tokens for r in reqs]
    for gamma in (2, 4):
        engine = SpeculativeEngine(cfg, params,
                                   ServeConfig(max_batch=3, max_len=96,
                                               kv_format="f32"),
                                   policy=get_policy("bf16"), gamma=gamma)
        reqs = [Request(uid=i, prompt=p, max_new=12)
                for i, p in enumerate(prompts)]
        stats = engine.serve(reqs)
        acc = stats["drafts_accepted"] / max(stats["drafts_proposed"], 1)
        spt = stats["decode_steps"] / max(stats["tokens"]
                                          - stats["prefills"], 1)
        ident = [r.out_tokens for r in reqs] == base_out
        print(f"  gamma={gamma}: acceptance={acc:.2f} "
              f"target steps/token={spt:.2f} "
              f"identical to baseline greedy: {ident}")
    print("  (< 1.0 target steps/token = the expensive datapath runs "
          "less than once per token)")

    # --- disaggregated engine API + async orchestrator (PR 4) ----------
    # Serving is now three separately jitted stages over one decode
    # state:  prefill(params, tokens, lengths) -> Prefix  (bucketed-
    # length prompt batch),  insert(prefix, state, slot)  (merge into a
    # free slot — paged prefixes scatter straight into pool pages), and
    # generate(params, state)  (one tick for the whole batch).  The
    # Orchestrator drives those stages from background threads with a
    # backpressured queue and per-token streaming callbacks.
    from repro.serve.orchestrator import (Orchestrator, OrchestratorConfig,
                                          StreamingRequest)
    print("\nAsync orchestrator (three-stage engine, streaming):")
    engine = ServingEngine(cfg, params,
                           ServeConfig(max_batch=3, max_len=96,
                                       kv_format="posit8"),
                           policy=get_policy("bf16"))
    pieces = []
    with Orchestrator(engine, OrchestratorConfig(max_queue=8)) as orch:
        sreqs = [StreamingRequest(p.tolist(), max_new=12,
                                  on_token=lambda r, ids, s:
                                  pieces.append(len(ids)))
                 for p in prompts]
        for s in sreqs:
            orch.submit(s, timeout=60.0)
        for s in sreqs:
            s.wait(120.0)
    ttfts = [s.ttft_s * 1e3 for s in sreqs]
    print(f"  {orch.stats['finished']} streams, "
          f"{sum(len(s.out_tokens) for s in sreqs)} tokens in "
          f"{len(pieces)} streamed callbacks; "
          f"median TTFT {sorted(ttfts)[len(ttfts) // 2]:.1f} ms")

    # --- observability (PR 5): spans, metrics, stage attribution -------
    # Every engine carries a span tracer and a metrics registry
    # (repro.obs).  With the tracer enabled, each engine stage's
    # dispatch (Python + jit dispatch) and each phase of the decode tick
    # (pages, logits copy, sampling, emit) is a span with exact self
    # times, so the host's wall clock decomposes per stage and phase.
    # Nothing waits for the device: device time per stage comes from a
    # profiler trace, where the same spans land on the device's clock.
    # Disabled (the default), the spans cost ~nothing.  The same
    # registry backs engine.stats / orch.stats, with latency histograms
    # (p50/p95/p99) per stage for free.
    from time import perf_counter

    from repro.obs import Tracer, format_breakdown, stage_breakdown
    print("\nObservability (span tracer + metrics registry):")
    engine = ServingEngine(cfg, params,
                           ServeConfig(max_batch=3, max_len=96,
                                       kv_format="posit8"),
                           policy=get_policy("bf16"),
                           tracer=Tracer(enabled=True))
    reqs = [Request(uid=i, prompt=p, max_new=12)
            for i, p in enumerate(prompts)]
    t0 = perf_counter()
    engine.serve(reqs)
    wall = perf_counter() - t0
    print(format_breakdown(stage_breakdown(engine.tracer, wall)))
    gen = engine.metrics.histogram("stage.generate.dispatch_s")
    print(f"  generate dispatch p50/p99: {gen.percentile(50) * 1e3:.1f}/"
          f"{gen.percentile(99) * 1e3:.1f} ms over {gen.count} calls")
    # serve under `with jax.profiler.trace("serve_trace"):` to get the
    # spans and the device's ops on one clock (jax.profiler.ProfileData,
    # TensorBoard or Perfetto); the CLI equivalent is
    # `python -m repro.launch.serve --trace-out DIR`

    # --- energy & SLO observability (PR 8) -----------------------------
    # EnergyAccountant prices each jitted stage from its *compiled* HLO:
    # MAC flops (dot/conv only — the posit fake-quant emulation is the
    # modeled ALU's native datapath, never priced as flops) x the
    # paper's Table-IV pJ/MAC at the stage's TCPolicy bit widths, plus
    # packed-weight DRAM traffic at 20 pJ/byte.  Multiplied by the live
    # per-stage call counters this gives joules/token next to tok/s —
    # the measurement half of ROADMAP direction 6.
    from repro.obs import EnergyAccountant, format_energy
    print("\nEnergy accounting (modeled, paper Table-IV pJ/MAC):")
    acct = EnergyAccountant(engine)
    print(format_energy(acct.breakdown()))
    # Per-request lifecycle + SLOs: with an Orchestrator, every request
    # carries six stamps (submit -> admit -> prefill_done -> insert_done
    # -> first_token -> finish), so TTFT decomposes into queue-wait vs
    # prefill vs insert (req.lifecycle_deltas()).  OrchestratorConfig
    # (ttft_slo_s=, itl_slo_s=) maintains orch.slo.* violation counters,
    # and request_log="out.jsonl" appends one JSON line per terminal
    # request.  CLI: python -m repro.launch.serve --energy \
    #   --request-log out.jsonl --ttft-slo 200 --itl-slo 50
    # CI gates the trajectory: scripts/bench_compare.py diffs every
    # bench's joules/token, acceptance rate, and latency percentiles
    # against benchmarks/baselines/.

    # --- robustness: chaos-hardened serving (PR 9) ---------------------
    # Deterministic fault injection (repro.serve.faults): a FaultPlan
    # schedules failures by call-site + call index — transient/persistent
    # stage errors, injected stragglers, dry page pools, NaN-poisoned
    # logits, crashed worker loops.  The hardened lifecycle survives it:
    # bounded exponential-backoff retry absorbs transient stage faults,
    # and the numeric guard (repro.serve.guard) quarantines any slot
    # whose logits come back non-finite and re-decodes JUST that slot up
    # a precision-escalation ladder derived from the serving policy
    # (posit8 -> posit16 -> full precision) — the paper's runtime
    # precision reconfiguration applied as a failure policy.  Neighbour
    # slots keep their logits bit-for-bit.
    from repro.serve import Fault, FaultPlan, RetryPolicy
    print("\nChaos hardening (fault injection + numeric guard):")
    plan = FaultPlan((
        Fault("stage_error", stage="generate", at=2, count=2),  # transient
        Fault("poison_logits", at=4, slot=0, fixed_by_level=2),  # NaN row
    ))
    engine = ServingEngine(cfg, params,
                           ServeConfig(max_batch=2, max_len=96),
                           policy=get_policy("paper_edge_p8"),
                           faults=plan, retry=RetryPolicy(), guard=True)
    reqs = [Request(uid=i, prompt=p, max_new=10)
            for i, p in enumerate(prompts[:4])]
    engine.serve(reqs)
    c = engine.metrics.snapshot()["counters"]
    print(f"  injected={int(c['faults.injected'])} "
          f"retries={int(c['stage.retries'])} "
          f"quarantined={int(c['guard.quarantined'])} "
          f"fallback_redecodes={int(c['guard.fallbacks'])} "
          f"-> all {sum(r.done and not r.error for r in reqs)}/4 "
          "requests completed")
    # Orchestrator lifecycle hardening: per-request deadlines
    # (StreamingRequest(deadline_s=...) or OrchestratorConfig.deadline_s
    # -> terminal error="deadline", slot + pages reclaimed), cancel()
    # honored mid-decode, a watchdog that fails in-flight requests if
    # the scheduler stalls (watchdog_s), and crash containment: any
    # worker-loop death finishes EVERY queued/in-flight request with an
    # error and flips orch.healthy — orch.health() snapshots liveness,
    # thread states and the fault/guard counters.  close() raises on
    # leaked threads instead of masking a stuck loop.  CLI:
    #   python -m repro.launch.serve --async \
    #     --fault-plan random:seed=3,n=6 --deadline-s 30 --health
    # The invariants (every request terminal, zero page leaks, un-faulted
    # streams token-identical to fault-free) live in tests/test_chaos.py.


if __name__ == "__main__":
    main()
