"""The benchmark's harness: builds the served path as ``repro.launch.serve
--async`` builds it, warms every shape a cell can reach, drives the cell's
traffic through the ``Orchestrator`` for a measured window, and checks what
the window served against the plain reference.

From the program it takes only the system under test (``ServingEngine``,
``Orchestrator``), its lifecycle stamps, its ``stage.*`` and ``engine.*``
counters, and the names of its programs and kernels in the device trace.
Everything else (traffic, weights, reference, operation and byte counts,
peaks, the trace reduction) is the benchmark's own.  What depends on a
model's architecture (its program config, weights, reference and work
counts) comes from the module of its family, ``bench/families/<family>.py``,
found by the name its configuration file gives.
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import gc
import itertools
import json
import shutil
import sys
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(ROOT / "src"))

import metrics_common          # noqa: E402
import trace as trace_mod      # noqa: E402
import traffic                 # noqa: E402

FAMILIES_DIR = BENCH_DIR / "families"

DRAIN_S = 60.0          # how long past the close an answer due may take
SAMPLE_REQUESTS = 8     # finished requests the reference checks: a fault
                        # in half of the slots escapes all 8 once in 256
TRACE_S = 6.0           # seconds of a --trace 1 window that are traced
WARM_THREADS = 4
# Rows one admission can hold, as warmed.  The orchestrator admits
# min(pending, free slots) rows in one prefill, and every (rows, bucket)
# pair is a program of its own (about 5 s of tracing, lowering and loading
# each on a v5e host, even from the persistent cache).  Below the knee an
# admission rarely holds more than 2: k rows need k requests arriving
# within one decode tick, or k slots freeing in one tick while k wait.  A
# closed loop admits as many rows as slots came free in one tick.  While
# its clients join, no more than WARM_ROWS wait for a free slot, and the
# traffic staggers the first round's answers: requests admitted together
# with one length come free together (five at once compiled inside the
# window).  Every run prints the rows its admissions held, and a compile
# inside the window fails the run (``window_compiles``, limit 0).
WARM_ROWS = 4
_LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# what the benchmark names
# ---------------------------------------------------------------------------

def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_parts(spec: dict, workload: str, traffic_dir: Path = None,
               limits_dir: Path = None, families_dir: Path = None):
    """(cell, config entry, config file, family module, traffic mix,
    limits) of a cell, each found by its name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    conf = json.loads((ROOT / entry["file"]).read_text())
    family = family_of(conf, families_dir)
    mix = traffic.load_mix(cell["traffic"],
                           traffic_dir or traffic.TRAFFIC_DIR)
    limits = json.loads(((limits_dir or BENCH_DIR / "limits")
                         / f"{workload}.json").read_text())
    return cell, entry, conf, family, mix, limits


def known_families(families_dir: Path = None) -> List[str]:
    return sorted(p.stem for p in (families_dir or FAMILIES_DIR).glob("*.py"))


def family_name(conf: dict) -> str:
    """A configuration file's ``"family"``; ``dense`` where it names none."""
    return conf.get("family", "dense")


def family_of(conf: dict, families_dir: Path = None):
    """The module ``bench/families/<family>.py`` of a configuration file's
    family: its ``program_cfg``, ``make_weights``, ``make_reference`` and
    ``make_cost`` (``bench/families/dense.py`` says what each gives)."""
    directory = families_dir or FAMILIES_DIR
    name = family_name(conf)
    known = known_families(directory)
    if name not in known:
        raise KeyError(f"{conf['name']}: no family {name!r} in {directory}; "
                       f"known: {known}")
    return metrics_common.load_by_name(directory, name)


def metric_reader(name: str):
    """The module ``bench/metrics/<name>.py`` (its ``read(ctx)``)."""
    return metrics_common.load_sibling(name)


# ---------------------------------------------------------------------------
# building the served path
# ---------------------------------------------------------------------------

def build_engine(family, conf: dict, params,
                 kv_format: Optional[str] = None):
    from repro.serve.engine import ServeConfig, ServingEngine
    s = conf["serving"]
    scfg = ServeConfig(max_batch=s["max_batch"], max_len=s["max_len"],
                       kv_format=kv_format or s["kv_format"],
                       kv_layout=s["kv_layout"], page_size=s["page_size"],
                       num_pages=s["num_pages"])
    return ServingEngine(family.program_cfg(conf), params, scfg,
                         policy=s["policy"])


class CompileCounter:
    """Counts lowerings (every compile, and every load from the persistent
    cache, lowers first) once armed.  One listener per process."""
    _one = None

    def __init__(self):
        self.count = 0
        self.armed = False
        self.names: List[str] = []

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._one is None:
            import jax.monitoring
            cls._one = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._one._on)
        cls._one.count, cls._one.armed, cls._one.names = 0, False, []
        return cls._one

    def _on(self, event, duration, fun_name="?", **_):
        if self.armed and event == _LOWERING:
            self.count += 1
            self.names.append(fun_name)


def reachable_buckets(eng, mix) -> List[int]:
    lo, hi, _ = traffic.length_range(mix)
    te = eng.engine
    return sorted({te.bucket_for(n) for n in (lo, hi)}
                  | {b for b in (2 ** k for k in range(4, 20))
                     if te.bucket_for(lo) < b < te.bucket_for(hi)})


def warm(eng, mix) -> int:
    """Compile and run once every program the cell reaches: prefill and
    insert for each (rows admitted, bucket) pair, rows up to WARM_ROWS, and
    generate.  Prefills compile concurrently; the inserts, which update
    the one decode state, take turns.  Returns the number of programs."""
    import jax
    import jax.numpy as jnp
    te = eng.engine
    rows = min(WARM_ROWS, eng.scfg.max_batch)
    shapes = [(r, b) for b in reachable_buckets(eng, mix)
              for r in range(rows, 0, -1)]
    lock = threading.Lock()

    def one(rb):
        r, b = rb
        toks = np.zeros((r, b), np.int32)
        prefix = te.prefill(eng.params, toks, np.full(r, b, np.int32))
        jax.block_until_ready(prefix)
        with lock:
            eng.cache = te.insert(prefix, eng.cache, 0, r - 1,
                                  dst_rows=np.zeros(b, np.int64))
            jax.block_until_ready(eng.cache)
        del prefix

    t = time.perf_counter()
    with cf.ThreadPoolExecutor(WARM_THREADS) as ex:
        list(ex.map(one, shapes))
    log(f"warm: {len(shapes)} prefill and insert programs "
        f"{time.perf_counter() - t:.3f} s")
    eng.cache["tok"] = jnp.zeros((eng.scfg.max_batch, 1), jnp.int32)
    eng.cache, logits = te.generate(eng.params, eng.cache)
    jax.block_until_ready(logits)
    reset_state(eng)
    return 2 * len(shapes) + 1


def reset_state(eng):
    import jax.numpy as jnp
    assert all(r is None for r in eng.slot_req)
    eng.cache = eng.engine.init_decode_state()
    eng.cache["page_table"] = jnp.asarray(eng._table)


def warm_serve(orch, eng, mix, vocab: int):
    """Serve short requests through the orchestrator until every slot has
    held one, so the host-side paths (admission, page tables, slot
    release) have run; at most WARM_ROWS are sent together."""
    from repro.serve.orchestrator import StreamingRequest
    lo = traffic.length_range(mix)[0]
    left = eng.scfg.max_batch + 1
    while left > 0:
        reqs = [StreamingRequest(list(range(1, lo + 1)), max_new=3)
                for _ in range(min(WARM_ROWS, left))]
        left -= len(reqs)
        for r in reqs:
            orch.submit(r)
        for r in reqs:
            if not r.wait(300.0) or r.error:
                raise RuntimeError(f"warm-up request failed: {r.error}")


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Rec:
    """One request as the harness saw it (perf_counter seconds)."""
    prompt: np.ndarray
    max_new: int
    due: float
    sreq: Any

    @property
    def tokens(self) -> List[float]:
        return self.sreq.token_t

    @property
    def admit(self) -> Optional[float]:
        r = self.sreq._req
        return None if r is None else r.timing.get("admit")

    @property
    def finished(self) -> bool:
        return self.sreq.done and self.sreq.error is None


@dataclasses.dataclass
class Window:
    open: float
    close: float
    recs: List[Rec]
    lateness_p99_s: float
    compiles: int
    busy_slots_at_open: int
    generate_calls: int      # the program's stage.generate.calls and
    decode_tokens: int       # engine.tokens over the ticks of the window
    admits: List[tuple]      # (end time, rows, bucket) of every admission

    @property
    def seconds(self) -> float:
        return self.close - self.open

    def due_in_window(self) -> List[Rec]:
        return [r for r in self.recs if self.open <= r.due < self.close]

    def admit_rows(self, lead: bool = False) -> Dict[tuple, int]:
        """(rows, bucket) of one admission -> admissions, in the window
        or, with ``lead``, before it opened."""
        hist: Dict[tuple, int] = {}
        for t, n, b in self.admits:
            if (t < self.open) if lead else (self.open <= t < self.close):
                hist[n, b] = hist.get((n, b), 0) + 1
        return dict(sorted(hist.items()))


class Recorder:
    """Host spans around the calls into each layer, for a traced run: a
    ``bench.*`` profiler annotation on each, and for ``generate`` the
    active slots and their live K/V rows, for ``prefill`` the true prompt
    tokens."""

    def __init__(self, eng):
        import jax
        self.gen: List[tuple] = []      # (t, active, live_rows)
        self.pre: List[tuple] = []      # (t, prompt tokens)
        te = eng.engine
        ann = jax.profiler.TraceAnnotation

        def wrap(obj, name, label, note=None):
            fn = getattr(obj, name)

            def wrapped(*a, **k):
                if note is not None:
                    note(*a, **k)
                with ann(label):
                    return fn(*a, **k)
            setattr(obj, name, wrapped)

        def note_gen(*_a, **_k):
            act = [i for i, r in enumerate(eng.slot_req) if r is not None]
            self.gen.append((time.perf_counter(), len(act),
                             int(sum(eng.slot_pos[i] + 1 for i in act))))

        def note_pre(params, tokens, lengths=None):
            self.pre.append((time.perf_counter(), int(np.sum(lengths))))

        wrap(te, "generate", "bench.stage.generate", note_gen)
        wrap(te, "prefill", "bench.stage.prefill", note_pre)
        wrap(te, "insert", "bench.stage.insert")
        wrap(eng, "step", "bench.engine.step")
        wrap(eng, "add_requests", "bench.engine.admit")
        wrap(eng, "_sample", "bench.host.sample")


class Tracer:
    """Runs the profiler over [start, start + seconds] from a helper
    thread, so the load generator never waits on it."""

    def __init__(self, log_dir: Path):
        self.log_dir = log_dir
        self.t = {}
        self.thread = None

    def schedule(self, start: float, seconds: float):
        import jax

        def body():
            time.sleep(max(0.0, start - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.log_dir),
                                     profiler_options=opts)
            self.t["mark_host"] = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.mark.t0"):
                pass
            self.t["start"] = self.t["mark_host"]
            time.sleep(max(0.0, self.t["start"] + seconds
                           - time.perf_counter()))
            self.t["stop"] = time.perf_counter()
            jax.profiler.stop_trace()

        self.thread = threading.Thread(target=body, name="bench-tracer")
        self.thread.start()

    def join(self):
        if self.thread is not None:
            self.thread.join()


def drive(orch, eng, plan, mix, seconds: float, counter: CompileCounter,
          tracer: Optional[Tracer] = None, more=None) -> Window:
    """Offer the plan's load, open the window after the mix's lead, close
    it ``seconds`` later, and wait for every answer the window is owed.
    A closed loop draws its requests past the end of ``plan`` from the
    iterator ``more``."""
    from repro.serve.orchestrator import StreamingRequest
    closed = mix["loop"] == "closed"
    seen = hook_engine(eng)
    t_load = time.perf_counter() + 0.01
    t_open = t_load + float(mix["lead_s"])
    t_close = t_open + seconds
    if tracer is not None:
        tracer.schedule(t_open, min(TRACE_S, seconds))
    recs: List[Rec] = []
    late: List[float] = []
    nxt = 0
    state = {"opened": False, "closed": False}
    busy_at_open = 0

    def send(p, due):
        s = StreamingRequest(p.prompt.tolist(), max_new=p.max_new)
        if not orch.submit(s):
            raise RuntimeError("the orchestrator refused a request")
        late.append(s.submit_t - due)
        recs.append(Rec(p.prompt, p.max_new, due, s))

    def next_planned():
        nonlocal nxt
        if nxt >= len(plan):
            if more is None:
                raise RuntimeError("the closed-loop plan ran out")
            plan.append(next(more))
        nxt += 1
        return plan[nxt - 1]

    # closed loop: clients join one by one over half the lead, and while
    # a slot is free no more than WARM_ROWS of them wait unadmitted, so
    # that no admission holds more rows than were warmed
    ramp: List[float] = []
    if closed:
        n = int(mix["clients"])
        ramp = [t_load + i * 0.5 * float(mix["lead_s"]) / n
                for i in range(n)]
        out: List[Rec] = []
    while True:
        now = time.perf_counter()
        if not state["opened"] and now >= t_open:
            state["opened"] = True
            busy_at_open = sum(r is not None for r in eng.slot_req)
            counter.count, counter.armed = 0, True
        if state["opened"] and not state["closed"] and now >= t_close:
            state["closed"] = True
            counter.armed = False

        if closed:
            if state["closed"]:
                break
            still = []
            for r in out:
                if r.sreq.done:
                    send(next_planned(), time.perf_counter())
                    still.append(recs[-1])
                else:
                    still.append(r)
            out = still
            while ramp and ramp[0] <= now:
                waiting = sum(1 for r in out if r.admit is None)
                serving = sum(1 for r in out if r.admit is not None
                              and not r.sreq.done)
                if waiting >= WARM_ROWS and serving < eng.scfg.max_batch:
                    break
                ramp.pop(0)
                send(next_planned(), time.perf_counter())
                out.append(recs[-1])
            time.sleep(0.002)
            continue
        # open loop: send what is due; after the close, stop once every
        # request due in the window has its answer
        if state["closed"]:
            owed = [r for r in recs if t_open <= r.due < t_close
                    and not r.sreq.done]
            if not owed or now > t_close + DRAIN_S:
                break
        # a request falls due at its time; no more than WARM_ROWS wait
        # unadmitted, so that a host stall, after which many are due at
        # once, never makes an admission of more rows than were warmed
        # (the wait counts in each one's TTFT and in the lateness)
        while nxt < len(plan) and t_load + plan[nxt].offset_s <= now:
            if sum(1 for r in recs[-WARM_ROWS:]
                   if r.admit is None and not r.sreq.done) >= WARM_ROWS:
                break
            send(plan[nxt], t_load + plan[nxt].offset_s)
            nxt += 1
        wake = [t_load + plan[nxt].offset_s] if nxt < len(plan) else []
        wake += [] if state["opened"] else [t_open]
        wake += [] if state["closed"] else [t_close]
        pause = min(wake, default=now + 0.005) - time.perf_counter()
        time.sleep(min(max(pause, 0.0), 0.005))
    # everything still in flight is not owed to the window: cancel it
    for r in recs:
        if not r.sreq.done:
            r.sreq.cancel()
    for r in recs:
        r.sreq.wait(DRAIN_S)
    if tracer is not None:
        tracer.join()
    if counter.count:
        log(f"warning: {counter.count} compilations inside the window: "
            f"{counter.names}")
    lat = float(np.percentile(late, 99)) if late else 0.0
    steps = [(c, k) for t, c, k in seen["step"] if t_open <= t < t_close]
    return Window(t_open, t_close, recs, lat, counter.count, busy_at_open,
                  sum(c for c, _ in steps), sum(k for _, k in steps),
                  seen["admit"])


def hook_engine(eng) -> Dict[str, List[tuple]]:
    """From now on record, for every admission ``eng`` makes, (end time,
    rows admitted, their bucket), and for every decode tick (end time, the program's
    ``stage.generate.calls`` and ``engine.tokens`` counted during it)."""
    seen: Dict[str, List[tuple]] = {"admit": [], "step": []}
    calls = eng.metrics.counter("stage.generate.calls")
    emitted = eng.metrics.counter("engine.tokens")
    add = getattr(eng, "_bench_add_requests", eng.add_requests)
    step = getattr(eng, "_bench_step", eng.step)
    eng._bench_add_requests, eng._bench_step = add, step

    def add_requests(reqs):
        ok = add(reqs)
        n = [len(eng._admission_tokens(r)) for r, k in zip(reqs, ok) if k]
        seen["admit"].append((time.perf_counter(), len(n),
                              eng.engine.bucket_for(max(n)) if n else 0))
        return ok

    def one_step():
        c, k = calls.value, emitted.value
        step()
        seen["step"].append((time.perf_counter(), calls.value - c,
                             emitted.value - k))
    eng.add_requests, eng.step = add_requests, one_step
    return seen


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------

def p95(xs) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, float), 95)) if len(xs) else None


def ttft_s(rec: Rec, w: Window) -> float:
    """From the time the request was due to its first token; a request
    with no token counts at the time it ended, or the end of the drain."""
    if rec.tokens:
        return rec.tokens[0] - rec.due
    end = rec.sreq.finish_t or (w.close + DRAIN_S)
    return end - rec.due


def e2e_values(w: Window) -> Dict[str, float]:
    due = w.due_in_window()
    gaps = [b - a for r in w.recs for a, b in zip(r.tokens, r.tokens[1:])
            if w.open <= b < w.close]
    toks = sum(1 for r in w.recs for t in r.tokens if w.open <= t < w.close)
    out = {"output_tok_s": toks / w.seconds}
    if due:
        out["ttft_p95_ms"] = 1e3 * p95([ttft_s(r, w) for r in due])
    if gaps:
        out["itl_p95_ms"] = 1e3 * p95(gaps)
    return out


def attempted_failed(w: Window, closed: bool):
    if closed:   # every request sent before the close; cancels don't count
        att = [r for r in w.recs if r.due < w.close]
        failed = [r for r in att if r.sreq.error not in (None, "cancelled")]
    else:
        att = w.due_in_window()
        failed = [r for r in att if not r.finished]
    return len(att), len(failed)


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def sample_for_check(w: Window, seed: int, closed: bool) -> List[Rec]:
    """Finished requests owed to the window, drawn from the seed: the
    longest and SAMPLE_REQUESTS - 1 others."""
    pool = [r for r in (w.recs if closed else w.due_in_window())
            if r.finished and r.due < w.close]
    if not pool:
        return []
    pool.sort(key=lambda r: (len(r.prompt) + len(r.sreq.out_tokens)))
    pick = [pool.pop()]
    order = traffic.rng_for(seed, 7).permutation(len(pool))
    return pick + [pool[i] for i in order[:SAMPLE_REQUESTS - 1]]


def check_width(mix) -> int:
    """The reference's one padded length for a mix: its longest prompt
    and answer, rounded up to 256."""
    _, hi, out = traffic.length_range(mix)
    return -(-(hi + out) // 256) * 256


def check(family, conf, params, w: Window, seed: int, closed: bool,
          limits: dict, mix: dict) -> Dict[str, dict]:
    """Every number compared, with its limit."""
    owed = w.recs if closed else w.due_in_window()
    unfinished = sum(1 for r in owed
                     if r.due < w.close and not r.sreq.done)
    short = sum(1 for r in owed if r.finished
                and len(r.sreq.out_tokens) != r.max_new)
    sample = sample_for_check(w, seed, closed)
    gap = float("inf")
    if sample:
        t = time.perf_counter()
        seqs = [np.concatenate([r.prompt, np.asarray(r.sreq.out_tokens[:-1],
                                                     np.int32)])
                for r in sample]
        gaps = family.make_reference(conf)(
            params, seqs, [len(r.prompt) for r in sample],
            served=[np.asarray(r.sreq.out_tokens, np.int32) for r in sample],
            width=check_width(mix))
        gap = max(float(g.max()) for g in gaps)
        log(f"check: the reference over {len(sample)} requests "
            f"{time.perf_counter() - t:.3f} s")
    served = sum(len(r.sreq.out_tokens) for r in sample)
    return {
        "max_logit_gap": {"value": gap, "limit": limits["max_logit_gap"],
                          "tokens": served, "requests": len(sample)},
        "unfinished": {"value": unfinished, "limit": 0},
        "short_answers": {"value": short, "limit": 0},
        "window_compiles": {"value": w.compiles, "limit": 0},
    }


def is_correct(checks: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


# ---------------------------------------------------------------------------
# faults planted under the timed path (tests only)
# ---------------------------------------------------------------------------

def plant_fault(eng, kind: str):
    """Break the served path underneath the harness:

    stale_state    generate returns its input state unchanged (no K/V row
                   written, no position advanced)
    half_batch     generate leaves out the second half of the slots, which
                   are given the logits of the first half
    token_altered  each sampled token is replaced by the next id
    """
    import jax
    te = eng.engine
    if kind == "stale_state":
        def stale(params, state):
            _, logits = te._generate_impl(params, state)
            return state, logits
        te._generate_jit = jax.jit(stale)
    elif kind == "half_batch":
        b = eng.scfg.max_batch

        def half(params, state):
            new, logits = te._generate_impl(params, state)
            h = b // 2
            return new, logits.at[h:].set(logits[: b - h])
        te._generate_jit = jax.jit(half, donate_argnums=(1,))
    elif kind == "token_altered":
        sample, vocab = eng._sample, eng.cfg.vocab

        def altered(*a, **k):
            return (np.asarray(sample(*a, **k)) + 1) % vocab
        eng._sample = altered
    else:
        raise ValueError(f"unknown fault {kind!r}")


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_info(trace_dev: Optional[dict] = None) -> dict:
    import jax
    devs = jax.devices()
    stats = devs[0].memory_stats() or {}
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs),
           "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    if trace_dev:
        out.update(trace_dev)
    return out


def enable_compile_cache():
    """JAX's persistent cache at a fixed path inside the checkout, unless
    JAX_COMPILATION_CACHE_DIR names one; every program is kept."""
    import os
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def new_orchestrator(eng):
    """The orchestrator as the served path runs it, with a queue that
    never refuses a request (the load is the traffic's to decide)."""
    from repro.serve.orchestrator import Orchestrator, OrchestratorConfig
    return Orchestrator(eng, OrchestratorConfig(
        max_queue=1 << 16, admission_timeout_s=float("inf"),
        detokenize=False))


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process: float, *, spec: Optional[dict] = None,
             kv_format: Optional[str] = None, fault: Optional[str] = None,
             traffic_dir: Path = None, limits_dir: Path = None,
             families_dir: Path = None) -> dict:
    """One run of a cell: the result object the benchmark prints."""
    import jax
    spec = spec or load_spec()
    cell, entry, conf, family, mix, limits = cell_parts(
        spec, workload, traffic_dir, limits_dir, families_dir)
    closed = mix["loop"] == "closed"
    counter = CompileCounter.get()
    t0 = time.perf_counter()
    params = family.make_weights(conf, seed)
    jax.block_until_ready(params)
    t_w = time.perf_counter()
    eng = build_engine(family, conf, params, kv_format)
    if fault:
        plant_fault(eng, fault)
    n_prog = warm(eng, mix)
    t_warm = time.perf_counter()
    orch = new_orchestrator(eng)
    vocab = conf["model"]["vocab_size"]
    warm_serve(orch, eng, mix, vocab)
    stream = traffic.iter_plan(mix, seed, vocab)
    plan = list(itertools.islice(stream, traffic.requests_needed(
        mix, seconds, DRAIN_S)))
    rec = Recorder(eng) if trace else None
    tracer = None
    if trace:
        trace_dir = ROOT / ".bench_trace" / workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = Tracer(trace_dir)
    t_ready = time.perf_counter()
    w = drive(orch, eng, plan, mix, seconds, counter, tracer, more=stream)
    orch.close()
    setup_s = w.open - t_process
    log(f"[{workload}] seed {seed}: weights {t_w - t0:.3f} s, engine and "
        f"warm-up of {n_prog} programs {t_warm - t_w:.3f} s, set-up to "
        f"load {t_ready - t_process:.3f} s, setup_s {setup_s:.3f}")
    log(f"[{workload}] generator lateness p99 {1e3 * w.lateness_p99_s:.3f} "
        f"ms; compilations in the window {w.compiles}; slots busy at open "
        f"{w.busy_slots_at_open}/{eng.scfg.max_batch}; requests sent "
        f"{len(w.recs)}; (rows, bucket) per admission in the lead-in "
        f"{w.admit_rows(lead=True)}, in the window {w.admit_rows()}")
    dev_extra, breakdown, per_layer = None, None, {}
    if trace:
        per_layer, dev_extra, breakdown = layer_metrics(
            spec, workload, family, conf, w, rec, tracer)
    device = device_info(dev_extra)
    values = e2e_values(w)
    att, failed = attempted_failed(w, closed)
    # free the program's state before the reference runs
    del orch, eng, rec, tracer
    gc.collect()
    checks = check(family, conf, params, w, seed, closed, limits, mix)
    if trace:
        metrics = per_layer
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        for m in spec["end_to_end"]:
            if m["name"] == "setup_s":
                continue
            if workload in m.get("workloads", [workload]) \
                    and m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    result = {"correct": is_correct(checks), "attempted": att,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


# ---------------------------------------------------------------------------
# per-layer metrics of a traced run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Ctx:
    """What a per-layer metric's reader may read."""
    conf: dict
    cost: Any                            # the family's make_cost(conf)
    peaks: dict
    window: Window
    trace: Optional[trace_mod.Trace]     # clipped to the traced window
    device: int
    t0: float                            # traced window, trace clock
    t1: float
    gen_calls: List[tuple]               # recorded in the traced window
    prefill_calls: List[tuple]


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[kind]


def layer_metrics(spec, workload, family, conf, w: Window, rec: Recorder,
                  tracer: Tracer):
    import jax
    tr = trace_mod.load(trace_mod.find_xplane(str(tracer.log_dir)))
    if "t0" not in tr.marks:
        raise RuntimeError("the trace holds no bench.mark.t0 span")
    off = tr.marks["t0"] - tracer.t["mark_host"]
    h0, h1 = tracer.t["start"], tracer.t["stop"]
    t0, t1 = h0 + off, h1 + off
    clipped = tr.window(t0, t1)
    dev = tr.devices[0] if tr.devices else 0
    ctx = Ctx(conf, family.make_cost(conf),
              peaks_for(jax.devices()[0].device_kind), w, clipped, dev,
              t0, t1, [g for g in rec.gen if h0 <= g[0] < h1],
              [p for p in rec.pre if h0 <= p[0] < h1])
    out = {}
    for m in spec["per_layer"]:
        if workload not in m.get("workloads", [workload]):
            continue
        v = metric_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    busy = np.mean([trace_mod.busy_seconds(clipped, d)
                    for d in (clipped.devices or [dev])])
    dev_extra = {"busy_s": float(busy), "window_s": t1 - t0}
    breakdown = {"device_ops": trace_mod.top_ops(clipped, dev),
                 "idle_gaps": trace_mod.top_gaps(clipped, dev, t0, t1)}
    shutil.rmtree(tracer.log_dir, ignore_errors=True)
    return out, dev_extra, breakdown
