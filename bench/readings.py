"""Readings that a cell's limits are set from: the number ``correct``
compares (``max_logit_gap``), read on the program over many seeds and on
the control over a few, at the cell's own size and load.

    python3 bench/readings.py --workload <cell> --seeds 1-12 \\
        --control-seeds 13-15 --seconds 30

One process on the chip.  The engine is built and warmed once; for each
seed the weights are made anew from that seed and handed to the same
engine, the cell's traffic for that seed is served for a window of
``--seconds``, and the window's answers are checked against the
reference, exactly as ``run.py`` does.  The control is the program's own
next lower KV precision (posit4 for a posit8 cache), built and warmed the
same way.  One JSON line per seed.

With ``--sweep-rates`` (an open loop) the program's engine is first offered
each rate for a window, and the seeds are then read at 0.8 of the knee
found: the highest rate that holds, with every lower rate holding too.  A
rate holds when every request due is answered and the mean queue wait of
the window's last quarter is under a second (about 15 decode ticks).  For
each rate it prints the requests due and answered, TTFT and queue-wait
figures and the output rate; then ``{"knee": ...}`` and the rate used.

With ``--witness R,M`` it reads no window: on any device (the CPU too),
``R`` requests with prompts of 300-900 tokens and ``M`` new tokens each are
served to the end through ``ServingEngine.serve`` (one slot a request),
and the reference's widest gap over what they served is printed, for the
program's KV format (``--seeds``) and the control's (``--control-seeds``).
``--layers`` cuts the depth, so a large configuration fits a host.  This
is a witness of the number ``correct`` compares beside a chip, never a
device metric.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import itertools  # noqa: E402
import json      # noqa: E402
import sys       # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

LOWER_KV = {"posit16": "posit8", "posit8": "posit4"}


def seeds(text):
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def sweep(H, eng, mix, vocab, seed, seconds, rates):
    """Offer each rate for a window on the warmed engine; one JSON line
    per rate, then ``{"knee": ...}``.  Returns (rows, knee)."""
    import traffic
    rows = []
    for rate in rates:
        m = dict(mix, rate_per_s=rate)
        plan = traffic.make_plan(m, seed, vocab, traffic.requests_needed(
            m, seconds, H.DRAIN_S))
        orch = H.new_orchestrator(eng)
        w = H.drive(orch, eng, plan, m, seconds, H.CompileCounter.get())
        orch.close()
        H.reset_state(eng)
        due = w.due_in_window()
        q = [(r.due, r.admit - r.due) for r in due if r.admit is not None]
        quarter = w.seconds / 4
        first = [x for t, x in q if t < w.open + quarter]
        last = [x for t, x in q if t >= w.close - quarter]
        v = H.e2e_values(w)
        row = {"rate": rate, "due": len(due),
               "answered": sum(r.finished for r in due),
               "ttft_p50_ms": 1e3 * float(np.median(
                   [H.ttft_s(r, w) for r in due])),
               "ttft_p95_ms": v.get("ttft_p95_ms"),
               "itl_p95_ms": v.get("itl_p95_ms"),
               "output_tok_s": v["output_tok_s"],
               "qwait_first_ms": 1e3 * float(np.mean(first)) if first else None,
               "qwait_last_ms": 1e3 * float(np.mean(last)) if last else None,
               "lateness_p99_ms": 1e3 * w.lateness_p99_s,
               "compiles": w.compiles}
        rows.append(row)
        print(json.dumps(row), flush=True)
    knee = None
    for row in rows:
        if row["answered"] < row["due"] or (row["qwait_last_ms"] or 0) >= 1e3:
            break
        knee = row["rate"]
    print(json.dumps({"knee": knee}), flush=True)
    return rows, knee


def read_seeds(H, family, conf, mix, limits, workload, kv_format, seed_list,
               seconds, label, sweep_rates=None):
    import jax
    import traffic
    closed = mix["loop"] == "closed"
    first = seed_list[0] if seed_list else 1
    params = family.make_weights(conf, first)
    eng = H.build_engine(family, conf, params, kv_format)
    n = H.warm(eng, mix)
    orch = H.new_orchestrator(eng)
    vocab = conf["model"]["vocab_size"]
    H.warm_serve(orch, eng, mix, vocab)
    orch.close()
    H.log(f"[{label}] warm: {n} programs")
    if sweep_rates:
        _, knee = sweep(H, eng, mix, vocab, first, seconds, sweep_rates)
        if knee is None:
            raise SystemExit("readings.py: no swept rate holds")
        mix = dict(mix, rate_per_s=round(0.8 * knee, 2))
        print(json.dumps({"rate_per_s": mix["rate_per_s"]}), flush=True)
    for seed in seed_list:
        eng.params = params = None          # one set of weights at a time
        params = family.make_weights(conf, seed)
        jax.block_until_ready(params)
        eng.params = params
        H.reset_state(eng)
        stream = traffic.iter_plan(mix, seed, vocab)
        plan = list(itertools.islice(stream, traffic.requests_needed(
            mix, seconds, H.DRAIN_S)))
        orch = H.new_orchestrator(eng)
        w = H.drive(orch, eng, plan, mix, seconds, H.CompileCounter.get(),
                    more=stream)
        orch.close()
        checks = H.check(family, conf, params, w, seed, closed, limits,
                         mix)
        row = {"workload": workload, "label": label, "kv_format": kv_format,
               "seed": seed, "correct": H.is_correct(checks),
               **{k: v for k, v in H.e2e_values(w).items()},
               "checks": checks}
        print(json.dumps(row), flush=True)
    del eng
    return mix


def witness(H, family, conf, kv_format, seed, n_req, max_new, layers):
    import reference as R
    import traffic
    from repro.serve.engine import Request
    conf = json.loads(json.dumps(conf))
    if layers:
        conf["model"]["num_hidden_layers"] = layers
    conf["serving"]["max_batch"] = n_req
    params = family.make_weights(conf, seed)
    eng = H.build_engine(family, conf, params, kv_format)
    rng = traffic.rng_for(seed, 1)
    vocab = conf["model"]["vocab_size"]
    reqs = [Request(uid=i, prompt=rng.integers(0, vocab, int(n)).astype(
        np.int32), max_new=max_new)
        for i, n in enumerate(rng.integers(300, 900, n_req))]
    eng.serve(reqs)
    del eng
    seqs = [np.concatenate([np.asarray(r.prompt, np.int32),
                            np.asarray(r.out_tokens[:-1], np.int32)])
            for r in reqs]
    ref = family.make_reference(conf)(params, seqs,
                                      [len(r.prompt) for r in reqs])
    gaps = [float(R.served_gaps(lg, len(r.prompt),
                                np.asarray(r.out_tokens)).max())
            for lg, r in zip(ref, reqs)]
    print(json.dumps({"config": conf["name"], "layers":
                      conf["model"]["num_hidden_layers"], "seed": seed,
                      "kv_format": kv_format, "tokens": sum(
                          len(r.out_tokens) for r in reqs),
                      "max_logit_gap": max(gaps), "per_request": gaps}),
          flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--sweep-rates", default="",
                    help="open loop: first find the knee over these rates "
                         "and read at 0.8 of it")
    ap.add_argument("--witness", default="",
                    help="R,M: serve R requests of M new tokens to the end "
                         "on any device and print the reference's gap")
    ap.add_argument("--layers", type=int, default=0)
    args = ap.parse_args()
    import jax
    if args.witness:
        sys.path.insert(0, str(Path(__file__).resolve().parent))
        import harness as H
        _, _, conf, family, _, _ = H.cell_parts(H.load_spec(),
                                                args.workload)
        kv = conf["serving"]["kv_format"]
        n_req, max_new = (int(x) for x in args.witness.split(","))
        for fmt, group in ((kv, args.seeds), (LOWER_KV[kv],
                                               args.control_seeds)):
            for seed in seeds(group):
                witness(H, family, conf, fmt, seed, n_req, max_new,
                        args.layers)
        return 0
    if jax.devices()[0].platform != "tpu":
        print("readings.py: needs a TPU", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import harness as H
    H.enable_compile_cache()
    _, _, conf, family, mix, limits = H.cell_parts(H.load_spec(),
                                                   args.workload)
    kv = conf["serving"]["kv_format"]
    rates = [float(x) for x in args.sweep_rates.split(",") if x]
    if args.seeds or rates:
        mix = read_seeds(H, family, conf, mix, limits, args.workload, kv,
                         seeds(args.seeds), args.seconds, "program", rates)
    if args.control_seeds:
        read_seeds(H, family, conf, mix, limits, args.workload, LOWER_KV[kv],
                   seeds(args.control_seeds), args.seconds, "control")
    return 0


if __name__ == "__main__":
    sys.exit(main())
