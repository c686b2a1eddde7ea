"""BENCHMARK.json and the files it names, against the contract's rules."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def all_metrics() -> list:
    b = bench()
    return b["end_to_end"] + b["per_layer"]


def test_top_level_keys_and_limits():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 4)
    for path in b["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))


@pytest.mark.parametrize("entry", all_metrics(), ids=lambda m: m["name"])
def test_metric_names_units_and_keys(entry):
    assert NAME.match(entry["name"])
    assert UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    per_layer = "layer" in entry
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(entry) <= allowed
    sources = {"device_trace", "program_span", "program_counter", "host_clock"}
    assert entry["source"] in (
        sources if per_layer else {"host_clock", "device_trace"})
    if not per_layer:
        assert 0.01 <= entry["bound"] <= 0.1


def test_names_are_unique_and_well_formed():
    b = bench()
    for group in (b["configs"], b["workloads"], all_metrics()):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for w in b["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and w["chips"] in (1, 4)
    pairs = [(w["config"], w["traffic"]) for w in b["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert "setup_s" in [m["name"] for m in b["end_to_end"]]


def cells_reporting(metric: dict) -> set:
    """The cells a metric is reported in: those it lists, else every cell;
    a per-layer metric only where the metric it moves is reported."""
    b = bench()
    cells = set(metric.get("workloads", [w["name"] for w in b["workloads"]]))
    if "moves" in metric:
        moved = {m["name"]: m for m in b["end_to_end"]}[metric["moves"]]
        cells &= cells_reporting(moved)
    return cells


@pytest.mark.parametrize("entry", bench()["per_layer"], ids=lambda m: m["name"])
def test_moves_is_an_end_to_end_metric_of_every_cell_that_reports_it(entry):
    e2e = {m["name"]: m for m in bench()["end_to_end"]}
    assert entry["moves"] in e2e
    assert cells_reporting(entry), "read in no cell"
    if "workloads" in entry:
        assert set(entry["workloads"]) <= cells_reporting(e2e[entry["moves"]])
    reader = os.path.join(BENCH, "layer_metrics", entry["name"] + ".py")
    assert os.path.isfile(reader), "one reader file for each per-layer metric"
    with open(reader) as f:
        text = f.read()
    assert "Layer:" in text and "Source:" in text and "Moves: " + entry["moves"] in text


def test_one_reader_for_each_per_layer_metric_and_no_other():
    files = {f[:-3] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
             if f.endswith(".py")}
    assert files == {m["name"] for m in bench()["per_layer"]}


@pytest.mark.parametrize("cell", bench()["workloads"], ids=lambda w: w["name"])
def test_every_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    b = bench()
    e2e = {m["name"] for m in b["end_to_end"] if cell["name"] in cells_reporting(m)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert [m for m in b["per_layer"] if cell["name"] in cells_reporting(m)]


@pytest.mark.parametrize("entry", bench()["configs"], ids=lambda c: c["name"])
def test_each_cell_has_limits_of_its_own_readings(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        spec = json.load(f)["check"]
    limits = spec["limits"]
    assert 0 < limits["gap_mean"] < limits["gap_max"] < 1
    assert limits["min_checked_tokens"] >= 100
    assert spec["max_requests"] >= 12, "a sample of every client, or a dozen"
    assert "PERF.md" in spec["limits_set_from"]


@pytest.mark.parametrize("entry", bench()["configs"], ids=lambda c: c["name"])
def test_configuration_files_say_what_they_are(entry):
    assert entry["file"].startswith("benchmarks/")
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"] and len(entry["reduced"]) <= 16
    for key in ("assumed", "deployment", "precision", "hbm_share", "engine",
                "reference", "check"):
        assert config.get(key), key
    width = re.compile(r"(hidden|intermediate|latent|state|head)_(size|dim)|_dim$|_rank$")
    assert not [k for k in entry["reduced"] if width.search(k)]
    assert os.path.isfile(
        os.path.join(BENCH, "reference", config["reference"] + ".py"))
    used = {w["config"] for w in bench()["workloads"]}
    assert entry["name"] in used


@pytest.mark.parametrize("cell", bench()["workloads"], ids=lambda w: w["name"])
def test_traffic_files_are_data_with_a_generator_and_a_sender(cell):
    path = os.path.join(BENCH, "traffic", cell["traffic"] + ".json")
    with open(path) as f:
        traffic = json.load(f)
    assert len(traffic["who"]) > 20
    assert os.path.isfile(
        os.path.join(BENCH, "generators", traffic["generator"] + ".py"))
