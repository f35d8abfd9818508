"""``BENCHMARK.json`` holds to the benchmark's contract, and every entry
resolves its configuration, traffic mix and metric readers by name."""
import copy
import json
import re

import pytest

from bench import harness

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
SPEC = harness.spec()
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((harness.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    names = [x["name"] for part in ("configs", "workloads", "end_to_end",
                                    "per_layer") for x in SPEC[part]]
    assert all(NAME.fullmatch(n) for n in names), names
    assert len(set(x["name"] for x in SPEC["end_to_end"] + SPEC["per_layer"])
               ) == len(SPEC["end_to_end"]) + len(SPEC["per_layer"])


def test_entries_have_exactly_their_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/configs/")
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower",
                                                             "higher")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    r = harness.resolve(cell)
    assert callable(harness.plugin("loops", r["mix"]["loop"]).Loop)
    assert harness.plugin("laws", r["config"]["jobs"]["law"])
    assert r["config"]["name"] == r["cell"]["config"]
    e2e = {m["name"] for m in r["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert r["per_layer"]
    for m in r["per_layer"]:
        assert m["moves"] in e2e
        assert callable(harness.reader(m["name"]))
    assert set(r["config"]["limits"]) >= {"unplanned", "feasible_mismatch",
                                          "regret", "emis_err"}


def test_a_new_cell_needs_only_an_entry():
    """A cell over an existing configuration and mix is one more entry:
    it resolves by name, and metrics without a ``workloads`` key reach
    it."""
    bench = copy.deepcopy(SPEC)
    bench["workloads"].append({"name": "ftn_overlay.served_w300",
                               "config": "ftn_overlay",
                               "traffic": "served_w300", "chips": 1,
                               "why": "x"})
    r = harness.resolve("ftn_overlay.served_w300", bench)
    assert r["mix"]["loop"] == "served"
    assert [m["name"] for m in r["end_to_end"]] == ["setup_s"]
    with pytest.raises(KeyError):
        harness.resolve("no.such_cell", bench)


def test_metric_files_exist_for_every_metric():
    for m in SPEC["per_layer"]:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").is_file()
    for w in SPEC["workloads"]:
        json.loads((harness.BENCH / "traffic" /
                    f"{w['traffic']}.json").read_text())
