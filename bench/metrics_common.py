"""Helpers the per-layer metric readers share: loading a reader by its
metric name from ``bench/metrics/<name>.py``, and the device idle share."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import trace as T

METRICS_DIR = Path(__file__).resolve().parent / "metrics"


def load_sibling(name: str):
    """The reader module of metric ``name`` (names may hold dots)."""
    path = METRICS_DIR / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def idle_share(ctx):
    if (ctx.trace is None or ctx.t1 <= ctx.t0
            or not (ctx.trace.ops.get(ctx.device)
                    or ctx.trace.modules.get(ctx.device))):
        return None
    busy = T.busy_seconds(ctx.trace, ctx.device)
    return 100.0 * (1.0 - busy / (ctx.t1 - ctx.t0))
