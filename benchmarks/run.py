"""Benchmark harness entry point: one benchmark per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run table3 ... # subset

Writes machine-readable results to benchmarks/results/*.json and prints
the ``name,us_per_call,derived`` summary CSV expected by the harness.
"""
from __future__ import annotations

import json
import os
import sys
import time

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")


def _entry(name):
    if name == "table3":
        from . import bench_table3_cycles as m
    elif name == "table4":
        from . import bench_table4_posit_designs as m
    elif name == "table5":
        from . import bench_table5_umac as m
    elif name == "table6":
        from . import bench_table6_vector as m
    elif name == "accuracy":
        from . import bench_accuracy as m
    elif name == "roofline":
        from . import roofline as m
    elif name == "kernels":
        from . import bench_kernels as m
    elif name == "kv_cache":
        from . import bench_kv_cache as m
    elif name == "paged_kv":
        from . import bench_paged_kv as m
    elif name == "speculative":
        from . import bench_speculative as m
    elif name == "serving":
        from . import bench_serving as m
    else:
        raise KeyError(name)
    return m


ALL = ("table3", "table4", "table5", "table6", "accuracy", "kernels",
       "kv_cache", "paged_kv", "speculative", "serving", "roofline")


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    names = sys.argv[1:] or ALL
    os.makedirs(RESULTS_DIR, exist_ok=True)
    csv = ["name,us_per_call,derived"]
    for name in names:
        t0 = time.time()
        out = _entry(name).main(verbose=True)
        dt_us = (time.time() - t0) * 1e6
        # canonical per-bench artifact; the modules write the same file
        # themselves, so this never forks a stale "{name}.json" duplicate
        path = os.path.join(RESULTS_DIR, f"BENCH_{name}.json")
        try:
            json.dump(out, open(path, "w"), indent=1, default=str)
        except TypeError:
            pass
        derived = ""
        if name == "table3":
            derived = f"exact={out['exact']}/{out['total']}"
        elif name == "table5":
            derived = (f"area={out['ratios']['area_x']:.1f}x;"
                       f"power={out['ratios']['power_x']:.1f}x")
        elif name == "table6":
            derived = (f"thr={out['ratios']['throughput_x']:.2f}x;"
                       f"eff={out['ratios']['energy_eff_x']:.2f}x")
        elif name == "accuracy":
            derived = (f"p32_orders={out['matmul32']['orders_better']:.1f}")
        elif name == "roofline":
            derived = f"cells={out['n_ok']}/{out['n_cells']}"
        elif name == "kernels":
            derived = f"max_err={out['max_rel_err']:.1e}"
        elif name == "paged_kv":
            derived = f"live/ring_p8={out['live_vs_ring']['posit8']:.2f}"
        elif name == "speculative":
            derived = (f"ident={out['all_identical']};"
                       f"tgt_steps={out['best_target_steps_per_token']:.2f}")
        elif name == "serving":
            knee = out["loads"][-1]
            derived = (f"loads={len(out['loads'])};"
                       f"p99_ttft_ms={knee['ttft_ms']['p99']:.0f}")
        csv.append(f"{name},{dt_us:.0f},{derived}")
        print()
    print("\n".join(csv))


if __name__ == "__main__":
    main()
