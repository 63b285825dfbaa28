"""The files behind ``BENCHMARK.json``: every name resolves, names and
units keep to the contract's characters, and a cell, a configuration
and a metric added as files alone are picked up."""

import json
import re
import shutil
from pathlib import Path

import pytest

from chipbench import harness, run

BENCH = json.loads(harness.BENCHMARK_JSON.read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(harness.BENCHMARK_JSON.read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_by_name(cell):
    c = harness.resolve_cell(cell)
    assert c.chips in (1, 4)
    assert hasattr(c.job, "run") and hasattr(c.job, "control")
    assert hasattr(c.reference, "init") and hasattr(c.reference, "forward")
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and len(c.per_layer) >= 1
    assert set(c.traffic["reports"].values()) | {"setup_s"} \
        == {m["name"] for m in c.end_to_end}
    for m in c.per_layer:
        reader = harness.load_module("layer_metrics", m["name"])
        assert callable(reader.read)


def test_names_units_and_entries():
    names = []
    for section, keys in (
            ("configs", {"name", "source", "file", "reduced", "why"}),
            ("workloads", {"name", "config", "traffic", "chips", "why"})):
        for e in BENCH[section]:
            assert set(e) == keys, e
            assert len(e["why"]) <= 200 and "\n" not in e["why"]
    for c in BENCH["configs"]:
        assert (harness.REPO / c["file"]).is_file()
        assert c["file"].startswith("chipbench/")
        on_file = json.loads((harness.REPO / c["file"]).read_text())
        assert sorted(on_file["reduced"]) == sorted(c["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in {"lower", "higher"}
        assert set(m.get("workloads", cells)) <= cells
        names.append(m["name"])
    names += [e["name"] for e in BENCH["configs"] + BENCH["workloads"]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert "setup_s" in e2e


def test_every_file_under_the_benchmark_has_a_contract_name():
    ok = re.compile(r"^[A-Za-z0-9_./-]+$")
    for p in harness.BENCH_DIR.rglob("*"):
        if "__pycache__" in p.parts or p.suffix == ".pyc":
            continue
        assert ok.match(str(p.relative_to(harness.REPO))), p


def test_added_files_are_picked_up_without_a_code_edit(tmp_path):
    """A later PR adds a cell, a configuration and a layer metric as
    files and entries; nothing that is there changes."""
    root = tmp_path / "bench"
    for kind in ("configs", "traffic", "layer_metrics"):
        (root / kind).mkdir(parents=True)
    tiny = Path(__file__).parent / "tiny"
    shutil.copy(tiny / "configs" / "bert-tiny.json",
                root / "configs" / "made-up-config.json")
    shutil.copy(tiny / "traffic" / "tiny_sync.json",
                root / "traffic" / "made_up_traffic.json")
    (root / "layer_metrics" / "made_up_metric.py").write_text(
        "def read(ctx):\n    return 42.0 if ctx['inputs'].get('x') else None\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "made-up-config", "source": "a test",
                             "file": "x", "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "made_up_cell",
                               "config": "made-up-config",
                               "traffic": "made_up_traffic", "chips": 1,
                               "why": "a test"})
    bench["per_layer"].append({
        "name": "made_up_metric", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "a test",
        "moves": "train_rate_sync", "workloads": ["made_up_cell"]})
    for m in bench["end_to_end"]:
        if m["name"] == "train_rate_sync":
            m["workloads"].append("made_up_cell")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.resolve_cell("made_up_cell", root / "BENCHMARK.json", root)
    assert cell.config["hidden_size"] == 32
    assert cell.job.__name__.endswith("fit_sync")
    assert [m["name"] for m in cell.per_layer] == ["made_up_metric"]
    result = harness.JobResult(0, 0, {}, 0, 0, [], (0, 0, 0),
                               layer_inputs={"x": 1})
    assert run.layer_metrics(cell, result, None, None, "TPU v5 lite") == {
        "made_up_metric": {"value": 42.0, "unit": "%"}}
    result.layer_inputs = {}
    assert run.layer_metrics(cell, result, None, None, "TPU v5 lite") == {}


def test_an_unknown_device_is_an_error():
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v99")
