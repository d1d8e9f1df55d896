"""The trace reduction: busy union, idle share, time per program and the
kernel match, on hand-made events and on a small trace recorded on a TPU
v5e (``fixtures/v5e-edge.xplane.pb``: a fraction of a second of
edge-chat-poisson)."""
from types import SimpleNamespace

import pytest

import metrics_common
import trace as T
from harness import BENCH_DIR

FIXTURE = BENCH_DIR / "tests" / "fixtures" / "v5e-edge.xplane.pb"


def hand_trace():
    ev = T.Event
    ops = [ev("%fusion.1 = bf16[4]{0} fusion(bf16[4]{0} %p)", 0.0, 1.0),
           ev("%fusion.2 = bf16[4]{0} fusion(bf16[4]{0} %q)", 0.5, 1.5),
           ev("%paged_decode_attention.3 = f32[16,4,3,64]{3,2,1,0} "
              "custom-call(s32[16,256]{1,0} %t)", 2.0, 2.5),
           ev("%fusion.1 = bf16[4]{0} fusion(bf16[4]{0} %p)", 4.0, 4.5),
           ev("%while.7 = (s32[]) while((s32[]) %tuple)", 0.0, 2.6)]
    mods = [ev("jit__generate_impl(7)", 0.0, 2.6), ev("jit_impl(9)", 4.0, 4.6)]
    host = [ev("bench.engine.step", 1.4, 4.1),
            ev("bench.host.sample", 2.6, 3.9)]
    return T.Trace({0: ops}, {0: mods}, host, {"t0": 0.0})


def test_union_and_idle():
    tr = hand_trace()
    assert T.busy_intervals(tr, 0) == [(0.0, 2.6), (4.0, 4.5)]
    assert T.busy_seconds(tr, 0) == pytest.approx(3.1)
    assert T.idle_gaps(tr, 0, 0.0, 5.0) == [(2.6, 4.0), (4.5, 5.0)]
    ctx = SimpleNamespace(trace=tr, device=0, t0=0.0, t1=5.0)
    assert metrics_common.idle_share(ctx) == pytest.approx(38.0)


def test_top_ops_group_by_instruction_and_skip_loops():
    top = dict(T.top_ops(hand_trace(), 0))
    assert top == {"fusion": pytest.approx(2.5),
                   "paged_decode_attention": pytest.approx(0.5)}


def test_program_and_kernel_time():
    tr = hand_trace()
    assert T.program_time(tr, 0, lambda p: p.endswith("_generate_impl")) \
        == (pytest.approx(2.6), 1)
    assert T.program_time(tr, 0, lambda p: p == "jit_impl")[1] == 1
    gen = metrics_common.load_sibling("paged_attn_roofline")
    assert T.op_time(tr, 0, gen.is_kernel) == (pytest.approx(0.5), 1)


def test_window_clips_and_labels_gaps():
    tr = hand_trace().window(1.0, 4.2)
    assert T.busy_seconds(tr, 0) == pytest.approx(1.6 + 0.2)
    gaps = T.top_gaps(hand_trace(), 0, 0.0, 5.0, n=2)
    assert gaps[0] == ["bench.host.sample", pytest.approx(1.4)]
    assert gaps[1][1] == pytest.approx(0.5)


@pytest.mark.skipif(not FIXTURE.is_file(), reason="no recorded trace")
def test_recorded_v5e_trace():
    tr = T.load(str(FIXTURE))
    assert tr.devices == [0]
    assert "t0" in tr.marks
    busy = T.busy_intervals(tr, 0)
    assert busy and all(s < t for s, t in busy)
    assert all(b[1] <= c[0] for b, c in zip(busy, busy[1:]))
    gen = metrics_common.load_sibling("generate_device_ms")
    secs, n = T.program_time(tr, 0, gen.is_generate)
    assert n > 0 and secs > 0
    att = metrics_common.load_sibling("paged_attn_roofline")
    ksecs, k = T.op_time(tr, 0, att.is_kernel)
    assert k > 0 and 0 < ksecs < secs
    assert any(e.name.startswith("bench.stage.generate") for e in tr.host)
