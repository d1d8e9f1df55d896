"""The one traffic generator: reads a mix from ``bench/traffic/<name>.json``
and turns it, with a seed, into a plan of requests.

A mix names its loop and its lengths; nothing else about a cell lives in
code.  Keys:

  loop        "open" (independent users, requests sent on a schedule) or
              "closed" (``clients`` callers, each sending its next request
              when its previous answer ends)
  rate_per_s  open loop: mean arrival rate
  bursts      open loop, optional: {"period_s", "on_s", "factor"}; the rate
              is ``factor`` times higher for the first ``on_s`` seconds of
              every period, and lower in between so the mean stays
              ``rate_per_s``
  clients     closed loop: number of callers
  lead_s      seconds of load before the measured window opens
  block       requests per block (see below)
  classes     [{"weight", "prompt": L, "output": L}], L = a lognormal
              {"median", "sigma", "min", "max"} in tokens

Every seed gets the same work.  Requests come in blocks of ``block``; each
block holds the same lengths (the class shares and the lognormal's
quantiles at (i + 0.5) / n) and, in an open loop, the same set of
exponential gaps, in an order that the seed shuffles.  Token ids are drawn
from the seed.  A window that spans whole blocks therefore sees the same
multiset of lengths and arrivals whatever the seed, and only their order
and content change.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Iterator, List, Optional

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    mix = json.loads(path.read_text())
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    return mix


@dataclasses.dataclass
class Planned:
    prompt: np.ndarray        # (n,) int32 token ids
    max_new: int
    offset_s: Optional[float]  # open loop: due time after the load starts


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A generator for one purpose (``stream``) of one seed; any whole
    number is a valid seed."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), stream])


def _lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def _class_counts(classes: List[dict], n: int) -> List[int]:
    w = np.array([c["weight"] for c in classes], float)
    raw = w / w.sum() * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts))[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def block_lengths(mix: dict, rng: np.random.Generator):
    """(prompt_lens, output_lens) of one block, shuffled by ``rng``."""
    n = int(mix["block"])
    prompts, outputs = [], []
    for cls, k in zip(mix["classes"], _class_counts(mix["classes"], n)):
        if k == 0:
            continue
        p = _lognormal_quantiles(cls["prompt"], k)
        o = _lognormal_quantiles(cls["output"], k)
        prompts.append(p)
        outputs.append(rng.permutation(o))   # pair prompts and outputs
    perm = rng.permutation(n)                 # anew for every seed
    return np.concatenate(prompts)[perm], np.concatenate(outputs)[perm]


def _unit_gaps(n: int, rng: np.random.Generator) -> np.ndarray:
    """The exponential's quantiles at (i + 0.5) / n, scaled to a mean of
    exactly 1 (unscaled, ten of them average 0.966), in the seed's order."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    return rng.permutation(q / q.mean())


def _warp(t_op: np.ndarray, mix: dict) -> np.ndarray:
    """Map arrival times of a unit-rate process to real seconds under the
    mix's rate (with its bursts, if any): invert the integrated rate."""
    rate = float(mix["rate_per_s"])
    b = mix.get("bursts")
    if not b:
        return t_op / rate
    period, on, factor = float(b["period_s"]), float(b["on_s"]), float(b["factor"])
    hi = rate * factor
    lo = (rate * period - hi * on) / (period - on)
    if lo < 0:
        raise ValueError("bursts: factor * on_s exceeds the period's load")
    per_period = rate * period          # unit-rate time per real period
    k = np.floor(t_op / per_period)
    r = t_op - k * per_period
    on_ops = hi * on
    within = np.where(r < on_ops, r / hi, on + (r - on_ops) / max(lo, 1e-12))
    return k * period + within


def iter_plan(mix: dict, seed: int, vocab: int) -> Iterator[Planned]:
    """The seed's requests, block after block, without end; any prefix of
    it is the plan of that many requests."""
    blk = int(mix["block"])
    len_rng, tok_rng, gap_rng = (rng_for(seed, 0), rng_for(seed, 1),
                                 rng_for(seed, 2))
    closed = mix["loop"] == "closed"
    frac = []
    if closed:
        # closed loop: the first round starts at once in every slot; cut
        # its answers to a residual-life share so the slots come free at
        # staggered times from the start, as they would in steady state.
        # No floor at the class's shortest answer: requests admitted
        # together with one length would come free in one tick.
        clients = int(mix["clients"])
        frac = rng_for(seed, 3).permutation((np.arange(clients) + 0.5)
                                            / clients).tolist()
    t_op = 0.0
    while True:
        p_lens, o_lens = block_lengths(mix, len_rng)
        gaps = None if closed else _unit_gaps(blk, gap_rng)
        for i in range(blk):
            toks = tok_rng.integers(0, vocab, int(p_lens[i]), dtype=np.int32)
            max_new, off = int(o_lens[i]), None
            if closed and frac:
                max_new = max(1, int(round(max_new * frac.pop(0))))
            if gaps is not None:
                t_op += gaps[i]
                off = float(_warp(np.array([t_op]), mix)[0])
            yield Planned(toks, max_new, off)


def make_plan(mix: dict, seed: int, vocab: int, n_requests: int
              ) -> List[Planned]:
    """``n_requests`` requests (rounded up to whole blocks) for one seed."""
    blk = int(mix["block"])
    n_blocks = max(1, math.ceil(n_requests / blk))
    return list(itertools.islice(iter_plan(mix, seed, vocab), n_blocks * blk))


def requests_needed(mix: dict, seconds: float, drain_s: float) -> int:
    """Requests a run plans ahead: the lead, the window and the drain
    after it at the mix's rate (open loop), or a first stretch for the
    clients (closed; the harness draws more from ``iter_plan`` when they
    have sent it all)."""
    if mix["loop"] == "open":
        b = mix.get("bursts")
        peak = float(mix["rate_per_s"]) * (float(b["factor"]) if b else 1.0)
        return int(math.ceil(peak * (mix["lead_s"] + seconds + drain_s))) + 1
    return int(mix["clients"]) * 64


def length_range(mix: dict):
    """(shortest, longest) prompt and the longest answer the mix sends."""
    lo = min(c["prompt"]["min"] for c in mix["classes"])
    hi = max(c["prompt"]["max"] for c in mix["classes"])
    out = max(c["output"]["max"] for c in mix["classes"])
    return lo, hi, out
