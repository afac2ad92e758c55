"""Every data file of the benchmark loads and names only what exists, in
the characters BENCHMARK.json allows."""

import glob
import json
import os
import re

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _load(kind):
    return {os.path.basename(p)[:-5]: json.load(open(p))
            for p in glob.glob(os.path.join(HERE, kind, "*.json"))}


BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIGS, CELLS, METRICS = (_load(k) for k in
                           ("configs", "workloads", "layer_metrics"))


def test_cells_name_a_config_a_job_and_a_generator_that_exist():
    assert CELLS and CONFIGS
    for name, cell in CELLS.items():
        assert NAME.match(name), name
        assert cell["config"] in CONFIGS, name
        assert os.path.exists(os.path.join(HERE, "jobs", cell["job"] + ".py"))
        assert cell["chips"] in (1, 4)
        assert 1 <= len(cell["why"]) <= 200
        for metric, unit in cell["end_to_end_units"].items():
            assert NAME.match(metric) and UNIT.match(unit), (name, metric)
    for name, cfg in CONFIGS.items():
        assert NAME.match(name), name
        assert 1 <= len(cfg["source"]) <= 200, name
        gen = cfg["data"]["generator"]
        assert os.path.exists(os.path.join(HERE, "data", gen + ".py")), name
        assert cfg["chips"] in (1, 4)


def test_layer_metrics_name_a_reader_a_job_and_an_end_to_end_metric():
    # a metric may move an end-to-end metric of a cell that BENCHMARK.json
    # does not list yet (higgs.predict): the cell's file names it
    end_to_end = {m["name"] for m in BENCH["end_to_end"]} | {
        m for c in CELLS.values() for m in c["end_to_end_units"]}
    jobs = {c["job"] for c in CELLS.values()}
    for name, m in METRICS.items():
        assert NAME.match(name) and UNIT.match(m["unit"]), name
        assert m["source"] in SOURCES and m["better"] in ("lower", "higher")
        assert m["moves"] in end_to_end, name
        assert set(m["jobs"]) <= jobs, name
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        assert os.path.exists(os.path.join(
            HERE, "readers", m["reader"]["kind"] + ".py")), name
        if name.endswith("_roofline"):
            assert m["unit"] == "%"


def test_benchmark_json_agrees_with_the_files():
    cells = {w["name"]: w for w in BENCH["workloads"]}
    for name, w in cells.items():
        assert CELLS[name]["config"] == w["config"]
        assert CELLS[name]["chips"] == w["chips"]
        assert CELLS[name]["job"] == w["traffic"]
    for c in BENCH["configs"]:
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        assert set(c["reduced"]) == set(CONFIGS[c["name"]]["reduced"])
        assert any(w["config"] == c["name"] for w in cells.values())
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        spec = METRICS[m["name"]]
        for key in ("unit", "better", "source", "layer", "moves"):
            assert m[key] == spec[key], (m["name"], key)
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)
            assert CELLS[cell]["job"] in spec["jobs"], (m["name"], cell)
    for name, m in e2e.items():
        for cell in m.get("workloads", []):
            if name != "setup_s":
                assert CELLS[cell]["end_to_end_units"][name] == m["unit"]
    table = json.load(open(os.path.join(HERE, "peaks.json")))
    assert all("source" in row for row in table.values())
