"""Drive the full dry-run matrix: every assigned (arch x shape) cell on the
single-pod (16,16) and multi-pod (2,16,16) production meshes.

Each cell runs in its own subprocess (jax device count is locked at first
init; per-cell isolation also bounds compiler memory).  Existing result
JSONs are skipped, so the sweep is resumable — rerun after a fix and only
failed cells recompile.

  PYTHONPATH=src python -m repro.launch.dryrun_all [--mesh pod1 pod2] \
      [--out benchmarks/results/dryrun]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from ..configs import cells


def run_cell(arch, shape, mesh, out, extra=()):
    name = f"{arch}_{shape}_{mesh}"
    path = os.path.join(out, name + ".json")
    if os.path.exists(path):
        return "cached", 0.0
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", shape, "--mesh", mesh, "--out", out, *extra],
        capture_output=True, text=True,
        # the children compile on placeholder CPU devices and must never
        # take an accelerator this machine may hold
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    dt = time.time() - t0
    if proc.returncode != 0:
        tail = proc.stderr.strip().splitlines()[-12:]
        json.dump({"arch": arch, "shape": shape, "mesh": mesh,
                   "error": "\n".join(tail)}, open(path + ".err", "w"),
                  indent=1)
        return "FAIL", dt
    return "ok", dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", nargs="+", default=["pod1", "pod2"])
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    ap.add_argument("--archs", nargs="*", default=None)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    results = {}
    t00 = time.time()
    for arch, shape, ok, why in cells():
        if args.archs and arch not in args.archs:
            continue
        for mesh in args.mesh:
            status, dt = run_cell(arch, shape, mesh, args.out)
            results[(arch, shape, mesh)] = status
            print(f"[{time.time() - t00:7.0f}s] {status:6s} "
                  f"{arch} {shape} {mesh} ({dt:.0f}s)", flush=True)
    fails = [k for k, v in results.items() if v == "FAIL"]
    print(f"\ndone: {len(results) - len(fails)}/{len(results)} ok")
    for k in fails:
        print("FAILED:", k)
    sys.exit(1 if fails else 0)


if __name__ == "__main__":
    main()
