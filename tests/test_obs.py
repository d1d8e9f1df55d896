"""Observability subsystem: tracer spans, metrics registry, breakdowns.

Covers the guarantees the serving stack leans on:

* span nesting/ordering and online self-time accounting (the basis of
  the per-stage wall-clock attribution);
* every span reaches a profiler session while one is active, on the
  profiler's clock, with its args as metadata; Python collections show
  as ``host.gc`` spans;
* histogram percentile accuracy vs exact numpy percentiles;
* registry snapshot round-trip (``from_snapshot(snap).snapshot() ==
  snap`` and JSON-stable);
* disabled-tracer overhead bound — the hot serving loop keeps its spans
  in place permanently, so ``span()`` with tracing off must stay cheap;
* ``StatsView`` legacy-dict facade semantics;
* end-to-end: a smoke ``ServingEngine`` served under a profiler session
  nests its decode-tick and admission phases as documented, never
  waits on the device for a span, and yields a consistent registry and
  a stage breakdown that attributes the wall clock.
"""
import contextlib
import gc
import glob
import json
import os
import threading
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       StatsView, Tracer, stage_breakdown)
from repro.obs.report import format_breakdown
from repro.obs.tracer import profiling


@contextlib.contextmanager
def profiled(log_dir):
    """Run the body under a profiler session; the list it yields is
    filled, after the session stops, with the host's events as
    ``(name, start_ns, end_ns, thread, raw event)`` records."""
    jax.profiler.start_trace(str(log_dir))
    evs = []
    try:
        yield evs
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                evs.append(SimpleNamespace(
                    name=e.name, start=e.start_ns,
                    end=e.start_ns + e.duration_ns,
                    thread=(plane.name, k), raw=e))


def stats(ev):
    """An event's metadata (read on demand: reading it is slow)."""
    return dict(ev.raw.stats)


def named(evs, name):
    return [e for e in evs if e.name == name]


def encloses(evs, outer, inner):
    """Every ``inner`` span lies inside an ``outer`` span of its thread
    (and there is at least one)."""
    ins = named(evs, inner)
    return bool(ins) and all(
        any(o.thread == e.thread and o.start <= e.start
            and e.end <= o.end for o in named(evs, outer))
        for e in ins)


# ---------------------------------------------------------------- tracer

def test_span_nesting_self_times():
    tr = Tracer(enabled=True)
    with tr.span("outer"):
        time.sleep(0.02)
        with tr.span("inner"):
            time.sleep(0.02)
    st = tr.self_times()
    assert set(st) == {"outer", "inner"}
    assert st["outer"]["count"] == 1 and st["inner"]["count"] == 1
    # outer total covers inner; outer SELF excludes it
    assert st["outer"]["total_s"] >= st["inner"]["total_s"]
    assert st["outer"]["self_s"] == pytest.approx(
        st["outer"]["total_s"] - st["inner"]["total_s"], abs=1e-6)
    # self times tile the outer wall: sum == outer total
    assert (st["outer"]["self_s"] + st["inner"]["self_s"]
            == pytest.approx(st["outer"]["total_s"], abs=1e-6))


def test_span_event_ordering(tmp_path):
    tr = Tracer(enabled=True)
    with profiled(tmp_path) as evs:
        with tr.span("a"):
            with tr.span("b"):
                pass
        with tr.span("c"):
            pass
    (a,), (b,), (c,) = named(evs, "a"), named(evs, "b"), named(evs, "c")
    # one clock: b nests in a, and a closes before c opens
    assert a.start <= b.start <= b.end <= a.end <= c.start <= c.end
    assert {k: v["count"] for k, v in tr.self_times().items()} \
        == {"a": 1, "b": 1, "c": 1}


@pytest.mark.parametrize("enabled", [False, True])
def test_spans_reach_the_profiler(tmp_path, enabled):
    """Whether or not the tracer keeps aggregates, a profiler session
    gets every span, decorator spans too, with its args as metadata."""
    tr = Tracer(enabled=enabled)

    @tr.trace("work")
    def work():
        pass

    assert not profiling()
    with profiled(tmp_path) as evs:
        assert profiling()
        with tr.span("stage.probe", cat="engine", n=3):
            work()
    (probe,), (w,) = named(evs, "stage.probe"), named(evs, "work")
    assert stats(probe) == {"n": 3}
    assert probe.start <= w.start <= w.end <= probe.end
    assert bool(tr.self_times()) is enabled


def test_gc_collection_is_a_host_gc_span(tmp_path):
    with profiled(tmp_path) as evs:
        gc.collect()
    spans = named(evs, "host.gc")
    assert any(stats(e).get("generation") == 2 for e in spans)
    assert all(e.end >= e.start for e in spans)
    # installed once per process, at import
    assert sum(cb.__name__ == "_gc_span" for cb in gc.callbacks) == 1


def test_trace_decorator_and_disabled_passthrough():
    tr = Tracer(enabled=True)

    @tr.trace("work", cat="host")
    def work(x):
        return x + 1

    assert work(1) == 2
    assert tr.self_times()["work"]["count"] == 1
    tr.disable()
    assert work(2) == 3                       # still callable, unrecorded
    assert tr.self_times()["work"]["count"] == 1


def test_thread_aware_stacks(tmp_path):
    """Spans on different threads must not see each other as parents."""
    tr = Tracer(enabled=True)
    go = threading.Event()

    def worker():
        go.wait(5)
        with tr.span("child_thread"):
            time.sleep(0.01)

    t = threading.Thread(target=worker, name="obs-worker")
    with profiled(tmp_path) as evs:
        with tr.span("main_span"):
            t.start()
            go.set()
            t.join(10)
    assert not t.is_alive()
    st = tr.self_times()
    # worker span is NOT a child of main_span: main self == main total
    assert st["main_span"]["self_s"] == pytest.approx(
        st["main_span"]["total_s"], abs=1e-6)
    # each thread's spans land on its own line of the profiler trace
    (main,), (child,) = named(evs, "main_span"), named(evs, "child_thread")
    assert main.thread != child.thread


def test_disabled_overhead_bound():
    """Hot-loop spans with tracing off and no profiler session must stay
    near-free (< ~5 µs/call, generous for CI noise; the real cost is one
    attr check, the profiler's is_enabled check and a return)."""
    tr = Tracer(enabled=False)
    assert not profiling()
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tr.span("hot"):
            pass
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 5e-6, f"disabled span costs {per_call * 1e6:.2f} µs"
    assert not tr.self_times()


def test_tracer_reset_and_capacity_validation():
    tr = Tracer(enabled=True)
    with tr.span("x"):
        tr.record("queue.wait", 0.0, 0.5, cat="queue")
    tr.reset()
    assert not tr.self_times() and tr.enabled
    with tr.span("x"):
        pass
    assert tr.self_times()["x"]["count"] == 1
    # no ring buffer, so nothing to size: aggregates are exact anyway
    with pytest.raises(TypeError):
        Tracer(capacity=8)


# --------------------------------------------------------------- metrics

def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("tokens")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = reg.gauge("depth")
    g.set(3)
    g.inc(-1)
    assert g.value == 2
    # get-or-create returns the same object; kind mismatch raises
    assert reg.counter("tokens") is c
    with pytest.raises(TypeError):
        reg.gauge("tokens")
    assert "tokens" in reg and "nope" not in reg


@pytest.mark.parametrize("dist", ["lognormal", "uniform"])
def test_histogram_percentiles_vs_numpy(dist):
    rng = np.random.default_rng(0)
    if dist == "lognormal":
        xs = rng.lognormal(mean=-6.0, sigma=1.5, size=5000)   # ~latencies
    else:
        xs = rng.uniform(1e-4, 1e-1, size=5000)
    h = Histogram("lat")
    for x in xs:
        h.observe(x)
    for q in (50, 95, 99):
        exact = float(np.percentile(xs, q))
        approx = h.percentile(q)
        # log-bucketed: relative error bounded by ~one bucket width
        assert abs(approx - exact) / exact < 0.10, (q, approx, exact)
    assert h.count == len(xs)
    assert h.mean == pytest.approx(float(xs.mean()), rel=1e-9)
    assert h.percentile(0) == pytest.approx(float(xs.min()))
    assert h.percentile(100) == pytest.approx(float(xs.max()))


def test_histogram_edge_cases():
    h = Histogram("h", lo=1e-3, hi=1e3)
    assert h.percentile(50) is None           # empty
    h.observe(0.0)                            # sub-lo bucket
    h.observe(1e9)                            # clamped to top bucket
    snap = h.snapshot()
    assert snap["count"] == 2
    assert snap["min"] == 0.0 and snap["max"] == 1e9
    # sub-lo bucket: all we know is "< lo", reported as lo at most
    assert 0.0 <= h.percentile(1) <= h.lo
    with pytest.raises(ValueError):
        Histogram("bad", lo=1.0, hi=0.5)


def test_registry_snapshot_roundtrip():
    reg = MetricsRegistry()
    reg.counter("engine.tokens").inc(42)
    reg.gauge("orch.queue_depth").set(7)
    h = reg.histogram("stage.generate.dispatch_s")
    rng = np.random.default_rng(1)
    for x in rng.lognormal(-5, 1, 300):
        h.observe(float(x))
    snap = reg.snapshot()
    # JSON-stable: survives a dump/load cycle
    snap2 = json.loads(json.dumps(snap))
    restored = MetricsRegistry.from_snapshot(snap2)
    assert restored.snapshot() == snap
    assert restored.counter("engine.tokens").value == 42
    assert (restored.histogram("stage.generate.dispatch_s").percentile(95)
            == pytest.approx(h.percentile(95)))


def test_stats_view_legacy_surface():
    reg = MetricsRegistry()
    sv = StatsView(reg, prefix="engine.")
    sv.bind_counters("tokens", "prefills")
    sv.bind_gauges("peak_live_pages")
    sv["tokens"] += 5                        # dict-style increment
    sv.update(prefills=3)                    # bulk update
    sv["peak_live_pages"] = 9
    assert {**sv} == {"tokens": 5, "prefills": 3, "peak_live_pages": 9}
    assert sv.get("missing", 0) == 0
    assert len(sv) == 3 and sorted(sv) == ["peak_live_pages", "prefills",
                                           "tokens"]
    # registry is the single source of truth
    assert reg.counter("engine.tokens").value == 5
    assert sv.metric_name("tokens") == "engine.tokens"
    # unknown keys auto-bind as gauges (late stats like wall_s)
    sv["evictions"] = 2
    assert reg.gauge("engine.evictions").value == 2
    # bulk reset, as bench warmups do
    sv.update(tokens=0, prefills=0)
    assert sv["tokens"] == 0 and reg.counter("engine.tokens").value == 0


# ---------------------------------------------------------------- report

def test_stage_breakdown_partitions():
    tr = Tracer(enabled=True)
    with tr.span("serve.step"):              # host bucket
        with tr.span("stage.generate", cat="engine"):
            time.sleep(0.01)
        with tr.span("engine.logits"):
            time.sleep(0.01)
    with tr.span("orch.detok", cat="detok"):  # concurrent: excluded
        time.sleep(0.01)
    wall = 0.05
    bd = stage_breakdown(tr, wall)
    g = bd["stages"]["generate"]
    assert set(g) == {"dispatch_s", "calls"} and g["calls"] == 1
    assert g["dispatch_s"] == pytest.approx(0.01, rel=0.5)
    assert bd["host"]["engine.logits"] == pytest.approx(0.01, rel=0.5)
    assert "serve.step" in bd["host"]
    assert "orch.detok" in bd["concurrent"]
    # attribution sums stages + host but NOT concurrent
    total = g["dispatch_s"] + sum(bd["host"].values())
    assert bd["attributed_s"] == pytest.approx(total, abs=1e-9)
    assert bd["attributed_s"] + bd["unattributed_s"] == pytest.approx(wall)
    assert 0 < bd["attributed_frac"] <= 1.0
    assert "generate" in format_breakdown(bd)


def test_stage_breakdown_since_window():
    tr = Tracer(enabled=True)
    with tr.span("stage.a", cat="engine"):
        time.sleep(0.01)
    snap = tr.self_times()
    with tr.span("stage.b", cat="engine"):
        time.sleep(0.01)
    bd = stage_breakdown(tr, 0.02, since=snap)
    assert "b" in bd["stages"] and "a" not in bd["stages"]
    # full-history breakdown still sees both
    assert set(stage_breakdown(tr, 0.02)["stages"]) == {"a", "b"}


# ----------------------------------------------------- engine integration

@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A smoke paged posit8 engine with its tracer enabled, serving three
    requests under a profiler session, with ``jax.block_until_ready``
    made to raise: no span may wait for the device."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.serve.engine import Request, ServeConfig, ServingEngine

    cfg = get_config("paper-edge", smoke=True)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    scfg = ServeConfig(max_batch=2, max_len=64, kv_format="posit8",
                       kv_layout="paged")
    eng = ServingEngine(cfg, params, scfg, tracer=Tracer(enabled=True))
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, 6), max_new=4)
            for i in range(3)]

    def no_sync(*_a, **_k):
        raise AssertionError("a stage span waited for the device")

    with pytest.MonkeyPatch.context() as mp, \
            profiled(tmp_path_factory.mktemp("serve-trace")) as evs:
        mp.setattr(jax, "block_until_ready", no_sync)
        t0 = time.perf_counter()
        stats = eng.serve(reqs)
        wall = time.perf_counter() - t0
    return SimpleNamespace(eng=eng, stats=stats, wall=wall, evs=evs)


def within(outer, evs):
    return [e for e in evs if e.thread == outer.thread
            and outer.start <= e.start and e.end <= outer.end]


@pytest.mark.parametrize("inner", ["engine.pages", "stage.generate",
                                   "engine.logits", "engine.sample",
                                   "engine.emit"])
def test_decode_tick_phases_nest_in_engine_step(served, inner):
    evs = served.evs
    steps = named(evs, "engine.step")
    assert len(steps) == served.stats["decode_steps"]
    # every tick holds each of its phases exactly once
    assert all(len(within(s, named(evs, inner))) == 1 for s in steps)
    assert not any(within(s, named(evs, "engine.admit")) for s in steps)


@pytest.mark.parametrize("inner", ["engine.pages", "stage.prefill",
                                   "stage.insert"])
def test_admission_phases_nest_in_engine_admit(served, inner):
    evs = served.evs
    admits = named(evs, "engine.admit")
    assert admits
    n = [len(within(a, named(evs, inner))) for a in admits]
    if inner == "engine.pages":               # each attempt reserves once
        assert set(n) == {1}
        return
    # every stage call of admission lies inside one
    assert sum(n) == len(named(evs, inner)) > 0
    if inner == "stage.insert":               # one insert per prompt row
        assert sum(n) == served.stats["prefills"]
    else:                                     # one prefill an admission
        assert max(n) == 1


def test_staged_never_syncs(served, monkeypatch):
    """With the tracer enabled, a stage call neither blocks on its
    outputs nor reads them back: one span, one counter, one dispatch
    histogram entry."""
    te = served.eng.engine
    assert te.tracer.enabled

    def no_sync(*_a, **_k):
        raise AssertionError("_staged waited for the device")

    monkeypatch.setattr(jax, "block_until_ready", no_sync)
    out = te._staged("probe", lambda x: x + 1, jax.numpy.ones(4))
    np.testing.assert_array_equal(np.asarray(out), 2.0)
    snap = served.eng.metrics.snapshot()
    assert snap["counters"]["stage.probe.calls"] == 1
    assert snap["histograms"]["stage.probe.dispatch_s"]["count"] == 1
    assert "stage.probe.device_s" not in snap["histograms"]
    assert served.eng.tracer.self_times()["stage.probe"]["cat"] == "engine"


def test_serving_engine_observability(served):
    eng, stats = served.eng, served.stats
    # legacy stats keys are live views of the registry
    snap = eng.metrics.snapshot()
    assert stats["tokens"] == snap["counters"]["engine.tokens"]
    assert stats["prefills"] == snap["counters"]["engine.prefills"]
    # per-stage latency histograms recorded one observation per call
    assert (snap["histograms"]["stage.generate.dispatch_s"]["count"]
            == stats["decode_steps"])
    assert (snap["histograms"]["stage.prefill.dispatch_s"]["count"]
            == snap["counters"]["stage.prefill.calls"])
    assert not any(k.endswith(".device_s") for k in snap["histograms"])

    # breakdown attributes the serve loop's wall clock, tick phases apart
    bd = stage_breakdown(eng.tracer, served.wall)
    assert {"prefill", "insert", "generate"} <= set(bd["stages"])
    assert {"engine.step", "engine.admit", "engine.logits",
            "engine.sample", "engine.emit"} <= set(bd["host"])
    assert bd["attributed_frac"] >= 0.9
    # the profiler got the same spans the aggregates counted
    st = eng.tracer.self_times()
    for name in ("engine.step", "engine.admit", "stage.generate"):
        assert len(named(served.evs, name)) == st[name]["count"]


@pytest.mark.parametrize("mode", [[], ["--async"]])
def test_launcher_trace_out_is_a_profiler_trace(monkeypatch, tmp_path,
                                                capsys, mode):
    """``launch/serve.py --trace-out DIR`` serves under a profiler session
    whose trace ``ProfileData`` loads, the engine's spans in it."""
    from repro.launch import serve
    # the launcher's persistent compile cache stays off under the tests
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    monkeypatch.setattr("sys.argv", [
        "serve", "--requests", "2", "--max-new", "3", "--batch", "2",
        "--max-len", "64", "--trace-out", str(tmp_path), *mode])
    assert serve.main() == 0
    assert not profiling()
    out = capsys.readouterr().out
    assert f"profiler trace -> {tmp_path}" in out and "generate" in out
    from jax.profiler import ProfileData
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for p in ProfileData.from_file(path).planes
             for line in p.lines for e in line.events}
    assert {"engine.step", "engine.admit", "stage.generate"} <= names
