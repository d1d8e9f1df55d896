"""The comparison that decides ``correct``, driven through a whole run at
CPU test size with the chip check skipped: a sound run comes out correct;
the posit4 control and each fault planted under the timed path come out
not correct."""
import json
import time

import pytest

import harness
from harness import BENCH_DIR

FX = BENCH_DIR / "tests" / "fixtures"
SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


def tiny_spec(cell, config="tiny.p8-paged"):
    spec = {"workloads": [{"name": cell, "config": "tiny",
                           "traffic": cell, "chips": 1, "why": "test"}],
            "configs": [{"name": "tiny",
                         "file": f"bench/tests/fixtures/{config}.json"}],
            "end_to_end": json.loads(json.dumps(SPEC["end_to_end"])),
            "per_layer": []}
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [cell]
    return spec


def limits_dir(config):
    """A fixture's own limits, ``limits-<config>/``, where it has them.
    The tied head's logits are small (a 0.02-scale embedding at width 64),
    so its gaps are too: sound runs read 0.002-0.016 and the posit4 control
    0.18-0.32 over seeds 1-8 and 11-16 on the CPU."""
    own = FX / f"limits-{config}"
    return own if own.is_dir() else FX / "limits"


def run(cell, seed, config="tiny.p8-paged", seconds=2.0, **kw):
    return harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                            spec=tiny_spec(cell, config),
                            traffic_dir=FX / "traffic",
                            limits_dir=limits_dir(config), **kw)


# every fixture configuration whose family has a module
CONFIGS = sorted(f.stem for f in FX.glob("*.json")
                 if harness.family_name(json.loads(f.read_text()))
                 in harness.known_families())


@pytest.mark.parametrize("config", CONFIGS)
def test_sound_run_is_correct(config):
    r = run("tiny-closed", 2 ** 31 + 7, config)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {m["name"] for m in SPEC["end_to_end"]}
    assert r["checks"]["max_logit_gap"]["tokens"] > 0


def test_open_loop_run_is_correct():
    r = run("tiny-open", 12)
    assert r["correct"], r["checks"]
    assert r["checks"]["unfinished"]["value"] == 0


@pytest.mark.parametrize("fault", ["stale_state", "half_batch",
                                   "token_altered"])
def test_planted_fault_is_not_correct(fault):
    # a half-batch fault shows only in requests served in the second slot:
    # a window long enough to finish a dozen requests on a loaded host
    r = run("tiny-closed", 3, seconds=4.0, fault=fault)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("config", CONFIGS)
def test_posit4_control_is_not_correct(config):
    r = run("tiny-closed", 4, config, kv_format="posit4")
    assert not r["correct"], r["checks"]


def test_traced_closed_run_reads_its_counters(monkeypatch):
    """A traced run reports the layer metrics that need no device trace
    (on the CPU the trace holds no TPU ops, so the others stay silent),
    and a compile inside the window is one of the numbers compared."""
    spec = tiny_spec("tiny-closed")
    spec["per_layer"] = [dict(m, workloads=["tiny-closed"])
                         for m in SPEC["per_layer"]
                         if m["moves"] == "output_tok_s"]
    peaks = harness.peaks_for("TPU v5 lite")
    monkeypatch.setattr(harness, "peaks_for", lambda kind: peaks)
    r = harness.run_cell("tiny-closed", 5, 2.0, True, time.perf_counter(),
                         spec=spec, traffic_dir=FX / "traffic",
                         limits_dir=FX / "limits")
    assert r["correct"], r["checks"]
    assert r["checks"]["window_compiles"] == {"value": 0, "limit": 0}
    occ = r["metrics"]["batch_occupancy"]["value"]
    assert 1.0 <= occ <= 2.0          # two slots, nearly always both busy
    assert r["device"]["window_s"] > 0


def test_closed_loop_ramp_admits_no_more_rows_than_warmed(monkeypatch):
    """While a slot is free, clients join only as fast as their requests
    are admitted WARM_ROWS at a time; a closed loop that has sent all of
    its first plan draws more from the seed's stream."""
    monkeypatch.setattr(harness, "WARM_ROWS", 1)
    spec = tiny_spec("tiny-closed")
    _, _, conf, family, mix, _ = harness.cell_parts(
        spec, "tiny-closed", FX / "traffic", FX / "limits")
    mix = dict(mix, clients=6, lead_s=0.0)   # all due at once
    vocab = conf["model"]["vocab_size"]
    eng = harness.build_engine(family, conf, family.make_weights(conf, 21))
    harness.warm(eng, mix)
    orch = harness.new_orchestrator(eng)
    harness.warm_serve(orch, eng, mix, vocab)
    stream = harness.traffic.iter_plan(mix, 21, vocab)
    plan = [next(stream) for _ in range(4)]
    w = harness.drive(orch, eng, plan, mix, 2.0,
                      harness.CompileCounter.get(), more=stream)
    orch.close()
    assert [n for _, n, _ in w.admits[:2]] == [1, 1]
    assert len(w.recs) > mix["clients"]


def test_open_loop_holds_back_past_the_warmed_rows(monkeypatch):
    """Requests that fall due together (as after a host stall) wait in
    the harness, not the orchestrator, beyond WARM_ROWS: no admission
    holds more rows than were warmed, and nothing compiles."""
    monkeypatch.setattr(harness, "WARM_ROWS", 1)
    spec = tiny_spec("tiny-open")
    _, _, conf, family, mix, _ = harness.cell_parts(
        spec, "tiny-open", FX / "traffic", FX / "limits")
    mix = dict(mix, rate_per_s=400.0, lead_s=0.2)
    vocab = conf["model"]["vocab_size"]
    eng = harness.build_engine(family, conf, family.make_weights(conf, 22))
    harness.warm(eng, mix)
    orch = harness.new_orchestrator(eng)
    harness.warm_serve(orch, eng, mix, vocab)
    plan = harness.traffic.make_plan(mix, 22, vocab, 64)
    counter = harness.CompileCounter.get()
    w = harness.drive(orch, eng, plan, mix, 0.5, counter)
    orch.close()
    assert max(n for _, n, _ in w.admits) == 1
    assert w.compiles == 0
