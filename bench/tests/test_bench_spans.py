"""The readers of the program's own spans (``tick_idle_ms``,
``tick_idle_ms.tput``, ``admit_idle_ms``) on hand-made trace events:
device idle time inside the spans named exactly ``engine.step`` or
``engine.admit``, per span, over the traced window."""
from types import SimpleNamespace

import pytest

import metrics_common
import trace as T
from harness import BENCH_DIR

FIXTURE = BENCH_DIR / "tests" / "fixtures" / "v5e-edge.xplane.pb"
tick = metrics_common.load_sibling("tick_idle_ms")
admit = metrics_common.load_sibling("admit_idle_ms")
tput = metrics_common.load_sibling("tick_idle_ms.tput")


def ctx_of(host, t0=0.0, t1=8.0):
    """Busy [0, 1], [2, 3], [5, 6]: idle (1, 2), (3, 5), (6, 8)."""
    ev = T.Event
    ops = [ev("%fusion.1 = f32[4]{0} fusion()", 0.0, 1.0),
           ev("%fusion.2 = f32[4]{0} fusion()", 2.0, 3.0),
           ev("%fusion.3 = f32[4]{0} fusion()", 5.0, 6.0)]
    tr = T.Trace({0: ops}, {}, [ev(n, s, t) for n, s, t in host],
                 {"t0": t0}).window(t0, t1)
    return SimpleNamespace(trace=tr, device=0, t0=t0, t1=t1)


def test_idle_inside_spans_counted_once():
    # a span over all of gap (1, 2), and two overlapping spans that share
    # gap (3, 5) in part: their union holds (3, 4.5) once
    ctx = ctx_of([("engine.step", 0.5, 2.5), ("engine.step", 2.8, 4.0),
                  ("engine.step", 3.5, 4.5)])
    assert tick.read(ctx) == pytest.approx(1e3 * (1.0 + 1.5) / 3)


def test_spans_cut_by_the_window_still_count():
    host = [("engine.step", -1.0, 1.5), ("engine.step", 7.0, 9.0)]
    ctx = ctx_of(host)
    assert len(ctx.trace.host) == 2
    assert tick.read(ctx) == pytest.approx(1e3 * (0.5 + 1.0) / 2)
    # a span wholly outside the window is not in it
    assert tick.read(ctx_of(host, t0=0.0, t1=6.5)) \
        == pytest.approx(1e3 * 0.5 / 1)


def test_admission_reads_engine_admit_only():
    ctx = ctx_of([("engine.admit", 3.0, 5.0), ("engine.step", 0.0, 2.0)])
    assert admit.read(ctx) == pytest.approx(2e3)
    assert tick.read(ctx) == pytest.approx(1e3)
    assert tput.read(ctx) == tick.read(ctx)


@pytest.mark.parametrize("reader", [tick, admit, tput])
def test_none_without_the_programs_span(reader):
    assert reader.read(ctx_of([])) is None
    # the harness's wrappers share a suffix but are not the program's
    ctx = ctx_of([("bench.engine.step", 0.0, 8.0),
                  ("bench.engine.admit", 0.0, 8.0),
                  ("engine.step.x", 0.0, 8.0), ("engine.admits", 0.0, 8.0)])
    assert reader.read(ctx) is None
    assert reader.read(SimpleNamespace(trace=None, device=0, t0=0.0,
                                       t1=1.0)) is None


def test_bench_wrapper_never_counts_beside_the_program_span():
    ctx = ctx_of([("bench.engine.step", 0.0, 8.0),
                  ("engine.step", 0.5, 1.5)])
    assert tick.read(ctx) == pytest.approx(500.0)


@pytest.mark.skipif(not FIXTURE.is_file(), reason="no recorded trace")
def test_recorded_trace_without_program_spans_reads_none():
    """A trace of a program that emits no ``engine.*`` spans (this one
    has the harness's ``bench.*`` spans only) reads nothing."""
    tr = T.load(str(FIXTURE))
    busy = T.busy_intervals(tr, 0)
    ctx = SimpleNamespace(trace=tr, device=0, t0=busy[0][0],
                          t1=busy[-1][1])
    assert any(e.name == "bench.engine.step" for e in tr.host)
    assert tick.read(ctx) is None and admit.read(ctx) is None
