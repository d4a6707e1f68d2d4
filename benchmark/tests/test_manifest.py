"""Names, units, layers and cross-references of BENCHMARK.json and the data
files, by the rules the driver checks before any run."""
import glob
import json
import os
import re

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LAYERS = {"entry", "scheduler", "step_program", "kernels", "device"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    M = json.load(f)


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def test_top_level_keys_and_limits():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["benchmark"] and M["command"][1].startswith("benchmark/")
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(1, len(M["workloads"]) // 4)


def test_names_units_and_entry_keys():
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        assert load("configs", f"{c['name']}.json")["reduced"] == c["reduced"]
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert w["config"] in {c["name"] for c in M["configs"]}
        data = load("workloads", f"{w['name']}.json")
        assert (data["config"], data["chips"], data["why"]) == (
            w["config"], w["chips"], w["why"])
        assert os.path.exists(os.path.join(HERE, "runners", data["runner"] + ".py"))
    names = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(names) == len(set(names))
    assert len({(w["config"], w["traffic"]) for w in M["workloads"]}) == len(M["workloads"])
    assert {c["name"] for c in M["configs"]} == {w["config"] for w in M["workloads"]}
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["layer"] in LAYERS and m["source"] in SOURCES
    for m in M["end_to_end"] + M["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")


def test_every_cell_reports_what_it_must():
    cells = [w["name"] for w in M["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in M["end_to_end"]}
    assert "workloads" not in next(m for m in M["end_to_end"] if m["name"] == "setup_s")
    for cell in cells:
        assert sum(cell in ws for n, ws in e2e.items() if n != "setup_s") >= 1
        assert any(cell in m.get("workloads", cells) for m in M["per_layer"])
    for m in M["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]], (m["name"], cell)


def test_every_per_layer_metric_has_its_reader_file():
    for m in M["per_layer"]:
        spec = load("layer_metrics", f"{m['name']}.json")
        assert os.path.exists(os.path.join(HERE, "readers", spec["reader"] + ".py"))
    listed = {m["name"] for m in M["per_layer"]}
    on_disk = {os.path.basename(p)[:-5] for p in
               glob.glob(os.path.join(HERE, "layer_metrics", "*.json"))}
    assert listed == on_disk


def test_run_py_names_no_cell_configuration_or_metric():
    with open(os.path.join(HERE, "run.py")) as f:
        code = f.read()
    for entry in M["configs"] + M["workloads"] + M["per_layer"] + M["end_to_end"]:
        if entry["name"] != "setup_s":    # the one metric the harness itself makes
            assert entry["name"] not in code, entry["name"]
