"""Chip smoke test: the posit-KV serving path at paper-edge full width on
one TPU chip.

    python chip_smoke.py [--seed N]

Runs from the repository root, in one process, and refuses to run (exit 2,
no result line) unless JAX's first device is a TPU.  The model is
``paper-edge`` at full size (12 x 768, 12/4 heads, d_ff 2048, vocab 32000,
~100M parameters) with random weights from ``--seed``, served under the
launcher's default policy (``paper_edge_p8``: posit8 weights, posit16
embeddings).  Four phases, each built the way ``repro.launch.serve``
builds it:

  a. ring-posit8     ``ServingEngine``, posit8 KV, ring layout, sync loop
  b. paged-posit8    posit8 KV, paged layout (page 16), driven through the
                     threaded ``Orchestrator`` as ``--async`` does
  c. paged-posit4    nibble-packed posit4 KV, paged layout, sync loop
  d. spec-paged      ``SpeculativeEngine``, gamma 4, posit8 paged target

Each phase serves 16 requests (prompt lengths drawn from the seed over
16-1024 tokens, 32 new tokens each, 8 slots, max_len 2048) twice: a cold
pass that compiles, then a warm pass whose wall time and tokens/s are
printed as smoke figures, not benchmark results.  A phase fails the script
when a request ends in an error or with fewer than 32 tokens, when any
logit a stage returns is non-finite, or when a compiled decode-stage
program holds no Mosaic kernel (``tpu_custom_call``).

Reference check: for two prompts, the engine's own stages (prefill,
insert, then four teacher-forced ``generate`` steps; ``verify`` on a
5-token chunk for the speculative target) are compared with a float32
``lm.forward`` of the same policy under
``jax.default_matmul_precision("highest")`` on the same chip, and the ring
and paged posit8 engines are compared with each other.  The measure is
max|engine - reference| / max|reference| over every compared logit.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Any failure raises before it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_REQUESTS = 16
PROMPT_LENS = (16, 1024)
MAX_NEW = 32
MAX_BATCH = 8
MAX_LEN = 2048
PAGE_SIZE = 16
GAMMA = 4
POLICY = "paper_edge_p8"           # the launcher's default policy
REF_PROMPTS = 2
REF_STEPS = 4

# Tolerance on max|engine - ref| / max|ref| per KV format.  The engine runs
# bf16 activations and weights (the model dtype); the reference runs f32 at
# "highest" matmul precision, with the same posit8 weights and an exact
# float KV.  bf16 carries 8 significant bits, so 12 layers of bf16
# matmuls, norms and residual adds leave a few parts in 10^2 of the logit
# scale on their own; every format's bound starts from that.
TOLERANCE = {
    # posit16 (es=2) keeps >= 10 fraction bits near the row scale: far
    # below bf16's own rounding, so the bf16 floor is the whole budget
    "posit16": 0.05,
    # posit8 (es=2) keeps <= 3 fraction bits near the row scale (relative
    # step 2^-4 per K/V value); attention averages many rows, so the
    # readout error shrinks, but it adds to the bf16 floor
    "posit8": 0.08,
    # posit4 (es=1) keeps at most 1 fraction bit: each K/V value may move
    # by up to a third of itself, so logits can drift by a large share of
    # their scale; the bound only catches a broken datapath (garbage or
    # NaN), not the format's coarse rounding
    "posit4": 0.5,
}
# ring vs paged posit8: identical codes and scales by construction; only
# the kernels' block walk (128-row blocks vs 16-row pages) changes the
# online-softmax summation order, an f32 effect far inside the bf16 floor
RING_VS_PAGED_TOL = 0.02

PHASES = (
    dict(name="ring-posit8", kv_format="posit8", layout="ring"),
    dict(name="paged-posit8", kv_format="posit8", layout="paged",
         orchestrator=True),
    dict(name="paged-posit4", kv_format="posit4", layout="paged"),
    dict(name="spec-paged", kv_format="posit8", layout="paged",
         speculative=True),
)
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (from its own
    monitoring events), so compilation is reported apart from serving."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration


class FiniteTap:
    """Wraps an engine's logit-returning stages and records any call whose
    logits hold a non-finite value."""

    def __init__(self, engine, label):
        self.bad = []
        for stage, pick in (("prefill", lambda out: out["logits"]),
                            ("generate", lambda out: out[1]),
                            ("verify", lambda out: out[1])):
            setattr(engine, stage, self._wrap(getattr(engine, stage),
                                              f"{label}.{stage}", pick))

    def _wrap(self, fn, name, pick):
        import numpy as np

        def tapped(*args, **kw):
            out = fn(*args, **kw)
            if not np.isfinite(np.asarray(pick(out), np.float32)).all():
                self.bad.append(name)
            return out
        return tapped


def make_requests(cfg, seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    return [rng.integers(0, cfg.vocab, int(n)).astype(np.int32)
            for n in lens]


def build_engine(cfg, params, phase):
    """The engine ``launch/serve.py`` builds for these flags."""
    from repro.serve.engine import ServeConfig, ServingEngine
    from repro.serve.speculative import SpeculativeEngine
    scfg = ServeConfig(max_batch=MAX_BATCH, max_len=MAX_LEN,
                       kv_format=phase["kv_format"],
                       kv_layout=phase["layout"],
                       page_size=PAGE_SIZE if phase["layout"] == "paged"
                       else None)
    if phase.get("speculative"):
        return SpeculativeEngine(cfg, params, scfg, policy=POLICY,
                                 gamma=GAMMA, draft_kv_format="posit8")
    return ServingEngine(cfg, params, scfg, policy=POLICY)


def stage_engines(eng):
    """(label, TransprecisionEngine) pairs whose stages a phase runs."""
    out = [("target", eng.engine)]
    if getattr(eng, "draft_engine", None) is not None:
        out.append(("draft", eng.draft_engine))
    return out


def serve_once(eng, prompts, orchestrated):
    """Serve every prompt to completion; returns (tokens, errors)."""
    if orchestrated:
        from repro.serve.orchestrator import (Orchestrator,
                                              OrchestratorConfig,
                                              StreamingRequest)
        orch = Orchestrator(eng, OrchestratorConfig(
            max_queue=64, admission_timeout_s=60.0, detokenize=False))
        streams = [StreamingRequest(p.tolist(), max_new=MAX_NEW)
                   for p in prompts]
        try:
            for s in streams:
                check(orch.submit(s), "a request timed out in admission")
            for s in streams:
                check(s.wait(timeout=600.0), "a stream never finished")
            check(orch.healthy, f"orchestrator unhealthy: {orch.health()}")
        finally:
            orch.close()
        return ([len(s.out_tokens) for s in streams],
                [s.error for s in streams if s.error is not None])
    from repro.serve.engine import Request
    reqs = [Request(uid=i, prompt=p, max_new=MAX_NEW)
            for i, p in enumerate(prompts)]
    eng.serve(reqs)
    return ([len(r.out_tokens) for r in reqs],
            [r.error for r in reqs if r.error is not None])


def stage_logits(eng, params, prompt, cont, verify):
    """Prefill ``prompt`` into slot 0 of a fresh decode state through the
    engine's own stages, then feed ``cont`` teacher-forced: one
    ``generate`` per token, or one ``verify`` chunk.  Returns
    (1 + len(cont), vocab) f32 logits."""
    import jax.numpy as jnp
    import numpy as np
    te = eng.engine
    n = len(prompt)
    bucket = te.bucket_for(n)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = prompt
    prefix = te.prefill(params, toks, np.asarray([n], np.int32))
    state = te.init_decode_state()
    dst = None
    if te.paged:                      # slot 0 owns pages 1.., others trash
        pmax = state["page_table"].shape[1]
        table = np.zeros((te.max_batch, pmax), np.int32)
        table[0] = 1 + np.arange(pmax)
        state["page_table"] = jnp.asarray(table)
        t = np.arange(bucket)
        dst = np.where(t < n, PAGE_SIZE + t, 0)
    state = te.insert(prefix, state, 0, 0, dst_rows=dst)
    out = [np.asarray(prefix["logits"][0], np.float32)]
    if verify:
        chunk = np.zeros((te.max_batch, len(cont)), np.int32)
        chunk[0] = cont
        state, logits = te.verify(params, state, chunk)
        out += list(np.asarray(logits[0], np.float32))
    else:
        for tok in cont:
            feed = np.zeros((te.max_batch, 1), np.int32)
            feed[0, 0] = tok
            state["tok"] = jnp.asarray(feed)
            state, logits = te.generate(params, state)
            out.append(np.asarray(logits[0], np.float32))
    del state
    return np.stack(out)[:, :eng.cfg.vocab]


def make_reference(cfg, params):
    """float32 ``lm.forward`` of the serving policy at "highest" matmul
    precision: logits (vocab,) at every position of a token sequence."""
    import jax
    import jax.numpy as jnp
    from repro.core.transprecision import get_policy
    from repro.models import lm
    cfg32 = dataclasses.replace(cfg, dtype_name="float32")
    params32 = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    policy = get_policy(POLICY)

    @jax.jit
    def fwd(p, tokens):
        with jax.default_matmul_precision("highest"):
            return lm.forward(p, {"tokens": tokens}, cfg32, policy)[0]

    def logits(seq, start, count):
        out = fwd(params32, jnp.asarray(seq, jnp.int32)[None])
        out = out[0, start:start + count]
        return jax.device_get(out[:, :cfg.vocab]).astype("float32")
    return logits


def rel_delta(got, want):
    import numpy as np
    return float(np.abs(got - want).max() / np.abs(want).max())


def compiled_kernels(te, stage):
    """True if the compiled program of ``stage`` holds a Mosaic kernel."""
    fn, args = te.stage_specs[stage]
    return "tpu_custom_call" in fn.lower(*args).compile().as_text()


def run_phase(phase, cfg, params, prompts, ref_cases, reference, clock):
    import numpy as np
    name = phase["name"]
    c0 = clock.seconds
    eng = build_engine(cfg, params, phase)
    taps = [FiniteTap(te, label) for label, te in stage_engines(eng)]
    spec = bool(phase.get("speculative"))

    # reference check through the engine's own stages
    deltas, logits = [], []
    for prompt, cont in ref_cases:
        cont = cont[:GAMMA + 1] if spec else cont[:REF_STEPS]
        got = stage_logits(eng, params, prompt, cont, verify=spec)
        want = reference(np.concatenate([prompt, cont]), len(prompt) - 1,
                         len(cont) + 1)
        deltas.append(rel_delta(got, want))
        logits.append(got)
    delta = max(deltas)

    results = {}
    for run in ("cold", "warm"):
        t0 = time.perf_counter()
        counts, errors = serve_once(eng, prompts, phase.get("orchestrator"))
        wall = time.perf_counter() - t0
        check(not errors, f"{name}: requests ended in errors: {errors}")
        short = [c for c in counts if c < MAX_NEW]
        check(not short, f"{name}: {len(short)} requests stopped short of "
                         f"{MAX_NEW} tokens: {short}")
        results[run] = dict(wall_s=wall, tokens=sum(counts))
    bad = [b for t in taps for b in t.bad]
    check(not bad, f"{name}: non-finite logits from {sorted(set(bad))}")
    for label, te in stage_engines(eng):
        for stage in te.stage_specs:
            if stage.split(".")[-1] in ("generate", "verify"):
                check(compiled_kernels(te, stage),
                      f"{name}: {label} {stage} holds no tpu_custom_call")
    tol = TOLERANCE[phase["kv_format"]]
    check(delta <= tol, f"{name}: logits differ from the f32 reference by "
                        f"{delta:.4g} of their scale (limit {tol})")
    warm = results["warm"]
    print(f"[{name}] compile_s={clock.seconds - c0:.2f} "
          f"cold_wall_s={results['cold']['wall_s']:.2f} "
          f"warm_wall_s={warm['wall_s']:.2f} tokens={warm['tokens']} "
          f"smoke_tok_per_s={warm['tokens'] / warm['wall_s']:.1f} "
          f"ref_rel_delta={delta:.4g} (limit {tol}, per prompt "
          f"{[round(d, 5) for d in deltas]})", flush=True)
    del eng
    return logits


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        sys.exit(2)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import numpy as np
    from repro.configs import get_config
    from repro.models import lm
    kind, count = dev.device_kind, len(jax.devices())
    print(f"jax {jax.__version__} | device {kind} x{count} | "
          f"compile cache {cache_dir}", flush=True)

    clock = CompileClock()
    cfg = get_config("paper-edge", smoke=False)
    params = lm.init_params(jax.random.PRNGKey(args.seed), cfg)
    prompts = make_requests(cfg, args.seed)
    rng = np.random.default_rng(args.seed + 1)
    ref_cases = [(p, rng.integers(0, cfg.vocab, GAMMA + 1).astype(np.int32))
                 for p in prompts[:REF_PROMPTS]]
    reference = make_reference(cfg, params)
    print(f"model {cfg.name}: {cfg.n_layers}x{cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads, vocab {cfg.vocab} | "
          f"{N_REQUESTS} requests, prompts "
          f"{min(map(len, prompts))}-{max(map(len, prompts))} tokens, "
          f"max_new {MAX_NEW}, batch {MAX_BATCH}, max_len {MAX_LEN}",
          flush=True)

    logits = {}
    for phase in PHASES:
        logits[phase["name"]] = run_phase(phase, cfg, params, prompts,
                                          ref_cases, reference, clock)
    ring_paged = max(rel_delta(a, b) for a, b in zip(
        logits["paged-posit8"], logits["ring-posit8"]))
    print(f"[ring-vs-paged posit8] rel_delta={ring_paged:.4g} "
          f"(limit {RING_VS_PAGED_TOL})", flush=True)
    check(ring_paged <= RING_VS_PAGED_TOL,
          "ring and paged posit8 logits disagree")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind, "count": count}}))


if __name__ == "__main__":
    main()
