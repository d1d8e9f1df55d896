"""BENCHMARK.json holds to its contract's shape, and every configuration,
traffic mix, limit and per-layer metric it names resolves by name to a
file of its own under bench/."""
import json
import re

import pytest

import harness
import metrics_common
import traffic
from harness import BENCH_DIR, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_keys():
    names = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert m["name"] not in names
        names.add(m["name"])
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"] for m in SPEC["end_to_end"]} >= {"setup_s"}


def test_every_cell_reports_enough():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}
    for cell in cells:
        ends = [n for n, m in e2e.items() if n != "setup_s"
                and cell in m.get("workloads", cells)]
        assert ends, cell
        layer = [m for m in SPEC["per_layer"]
                 if cell in m.get("workloads", cells)]
        assert layer, cell
        for m in layer:     # what a metric moves, the cell reports
            assert cell in e2e[m["moves"]].get("workloads", cells)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_parts_resolve_by_name(cell):
    w, entry, conf, family, mix, limits = harness.cell_parts(SPEC, cell)
    assert conf["name"] == w["config"] == entry["name"]
    assert sorted(conf["reduced"]) == sorted(entry["reduced"])
    assert mix["loop"] in ("open", "closed")
    assert limits["max_logit_gap"] > 0
    family.program_cfg(conf)             # the program agrees on every width


def test_configs_are_files_of_their_own():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        assert (ROOT / f).is_file()


@pytest.mark.parametrize("name", [m["name"] for m in SPEC["per_layer"]])
def test_metric_readers_resolve_by_name(name):
    assert callable(metrics_common.load_sibling(name).read)


def test_traffic_mixes_resolve_by_name():
    for w in SPEC["workloads"]:
        assert (traffic.TRAFFIC_DIR / f"{w['traffic']}.json").is_file()


def test_peaks_by_device_kind():
    p = harness.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert "source" in json.loads((BENCH_DIR / "peaks.json").read_text())


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks_for("TPU v9 imaginary")
