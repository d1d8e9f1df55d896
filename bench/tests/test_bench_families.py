"""A configuration's model family is a module of its own, found by the
name the configuration file gives (``bench/families/<family>.py``): a
whole run goes through the family's four functions, an unknown family
fails before any weights are made, and a file that names no family is
dense."""
import json
import time

import pytest

import harness
from harness import BENCH_DIR
from test_bench_correct import FX, SPEC, tiny_spec

# A family that wraps the dense one and logs each of its four functions as
# it is called.  Its work counts are a dataclass, as a family's are as a
# rule: the loader must enter the module in ``sys.modules`` for that.
COUNTING = '''
import dataclasses
from pathlib import Path

import harness
import metrics_common

dense = metrics_common.load_by_name(harness.FAMILIES_DIR, "dense")
LOG = Path({log!r})


def note(name):
    with LOG.open("a") as f:
        f.write(name + "\\n")


@dataclasses.dataclass(frozen=True)
class Cost:
    inner: object

    def generate_cost(self, active: int, live_rows: int):
        return self.inner.generate_cost(active, live_rows)

    def attention_cost(self, live_rows: int):
        return self.inner.attention_cost(live_rows)

    def prompt_flops(self, n: int) -> float:
        return self.inner.prompt_flops(n)

    def token_flops(self, keys: int) -> float:
        return self.inner.token_flops(keys)


def program_cfg(conf):
    note("program_cfg")
    return dense.program_cfg(conf)


def make_weights(conf, seed):
    note("make_weights")
    return dense.make_weights(conf, seed)


def make_reference(conf):
    note("make_reference")
    return dense.make_reference(conf)


def make_cost(conf, kv_format=None):
    note("make_cost")
    return Cost(dense.make_cost(conf, kv_format))
'''


def tiny_conf(family=None):
    conf = json.loads((FX / "tiny.p8-paged.json").read_text())
    if family is not None:
        conf["family"] = family
    return conf


def spec_for(tmp_path, cell, conf):
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(conf))
    spec = tiny_spec(cell)
    spec["configs"][0]["file"] = str(path)
    return spec


def test_a_whole_run_goes_through_the_family(tmp_path, monkeypatch):
    """A traced CPU run of a tiny cell whose file names a family found
    only in a ``families_dir`` of its own reaches all four functions."""
    fams, limits = tmp_path / "families", tmp_path / "limits"
    fams.mkdir()
    limits.mkdir()
    log = tmp_path / "calls.txt"
    (fams / "counting.py").write_text(COUNTING.format(log=str(log)))
    # a cell name of its own: a traced run keeps its trace under that name,
    # and another test file traces tiny-closed in another worker
    cell = "tiny-family"
    (limits / f"{cell}.json").write_text(
        (FX / "limits" / "tiny-closed.json").read_text())
    spec = spec_for(tmp_path, cell, tiny_conf("counting"))
    spec["workloads"][0]["traffic"] = "tiny-closed"
    spec["per_layer"] = [dict(m, workloads=[cell])
                         for m in SPEC["per_layer"]
                         if m["moves"] == "output_tok_s"]
    peaks = harness.peaks_for("TPU v5 lite")
    monkeypatch.setattr(harness, "peaks_for", lambda kind: peaks)
    r = harness.run_cell(cell, 9, 2.0, True, time.perf_counter(),
                         spec=spec, traffic_dir=FX / "traffic",
                         limits_dir=limits, families_dir=fams)
    assert r["correct"], r["checks"]
    assert "step_mfu.tput" in r["metrics"]      # read from make_cost's counts
    assert set(log.read_text().split()) == {
        "program_cfg", "make_weights", "make_reference", "make_cost"}


def test_an_unknown_family_fails_in_cell_parts(tmp_path):
    spec = spec_for(tmp_path, "tiny-closed", tiny_conf("nope"))
    with pytest.raises(KeyError,
                       match=r"no family 'nope'.*known: \['dense'\]"):
        harness.cell_parts(spec, "tiny-closed", FX / "traffic",
                           FX / "limits")


def test_no_family_key_is_dense():
    bare = harness.family_of(tiny_conf())
    named = harness.family_of(tiny_conf("dense"))
    assert bare.__file__ == named.__file__ == str(
        BENCH_DIR / "families" / "dense.py")
    assert bare.__name__ == named.__name__
