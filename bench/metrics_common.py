"""Helpers the per-layer metric readers and the model families share:
loading a module by its name from a directory of the benchmark's own
(``bench/metrics/<name>.py``, ``bench/families/<name>.py``), and the device
idle share."""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import trace as T

METRICS_DIR = Path(__file__).resolve().parent / "metrics"


def load_by_name(directory: Path, name: str):
    """The module ``<directory>/<name>.py`` (names may hold dots), loaded
    by its path.  It is entered in ``sys.modules`` before it runs, as
    ``dataclasses`` needs of a module that defines a dataclass."""
    path = Path(directory) / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no module {name!r} at {path}")
    key = f"bench_{path.parent.name}_{name}"
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        sys.modules.pop(key, None)
        raise
    return mod


def load_sibling(name: str):
    """The reader module of metric ``name``."""
    return load_by_name(METRICS_DIR, name)


def idle_share(ctx):
    if (ctx.trace is None or ctx.t1 <= ctx.t0
            or not (ctx.trace.ops.get(ctx.device)
                    or ctx.trace.modules.get(ctx.device))):
        return None
    busy = T.busy_seconds(ctx.trace, ctx.device)
    return 100.0 * (1.0 - busy / (ctx.t1 - ctx.t0))
