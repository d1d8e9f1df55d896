"""The benchmark's one entry point.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` (a configuration under a traffic mix)
on the chip this process finds, from the root of a checkout, and prints
one JSON object as the last line of standard output:
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
"checks"}``.  With ``--trace 0`` the metrics are the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the first seconds of the window.  ``checks`` holds every number
compared to decide ``correct``, each beside its limit; they are printed on
standard error as well, as its last lines.

It refuses to run (exit 2, no result) unless JAX's first device is a TPU
and there are as many as the cell asks for.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json      # noqa: E402
import sys       # noqa: E402
from pathlib import Path  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    root = Path(__file__).resolve().parent.parent
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        print(f"run.py: no workload {args.workload!r}; known: "
              f"{sorted(cells)}", file=sys.stderr)
        return 2
    import jax
    devs = jax.devices()
    need = int(cells[args.workload]["chips"])
    if devs[0].platform != "tpu" or len(devs) < need:
        print(f"run.py: needs {need} TPU chip(s); JAX found "
              f"{len(devs)} x {devs[0].platform!r}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import harness
    harness.enable_compile_cache()
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), T_PROCESS, spec=spec)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
