"""BENCHMARK.json and the files it names, against the contract's rules."""

import json
import os
import re

import pytest

from benchmarks.loading import FAMILY_ANSWERS, load_family

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
                "family", "reference", "check"):
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


# -- the model family a configuration names ----------------------------------------
MIN_EXPERTS_HELD = 8        # the model-configs guide's floors for a cut
MIN_VOCABULARY_SHARE = 8    # at least an eighth of the rows


def cut_faults(config: dict, reduced_fields: dict) -> list[str]:
    """What is wrong with a file's ``reduced`` list, given its family's map
    from keys under ``reduced`` to fields of the program's model. Every
    key is one the family can cut and has its published count beside it
    (``source_<key>``); a cut in the experts held or in the rows of the
    vocabulary also states the deployment (``chips_sharing_a_layer``) and
    keeps to the floors."""
    faults = []
    for key in config["reduced"]:
        if key not in reduced_fields:
            faults.append(f"{key}: not a key the family can cut")
            continue
        published = config.get("source_" + key)
        if not isinstance(published, int) or published < config[key]:
            faults.append(f"{key}: no published count source_{key} beside it")
            continue
        field = reduced_fields[key]
        if field not in ("moe.num_experts", "vocab_size"):
            continue
        chips = config.get("chips_sharing_a_layer")
        if not isinstance(chips, int) or chips < 1:
            faults.append(f"{key}: the file states no chips_sharing_a_layer")
        elif field == "moe.num_experts" and config[key] * chips < published:
            faults.append(f"{key}: {chips} chips of {config[key]} experts "
                          f"do not hold the published {published}")
        if field == "moe.num_experts" and config[key] < MIN_EXPERTS_HELD:
            faults.append(f"{key}: under {MIN_EXPERTS_HELD} routed experts")
        if (field == "vocab_size"
                and config[key] * MIN_VOCABULARY_SHARE < published):
            faults.append(f"{key}: under an eighth of the vocabulary")
    return faults


@pytest.mark.parametrize("entry", bench()["configs"], ids=lambda c: c["name"])
def test_every_configuration_names_a_family_that_answers(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    assert NAME.match(config["family"])
    assert os.path.isfile(
        os.path.join(BENCH, "families", config["family"] + ".py"))
    family = load_family(config)
    for name in FAMILY_ANSWERS:
        answer = getattr(family, name)
        assert callable(answer) or isinstance(answer, (dict, tuple)), name
    assert not cut_faults(config, family.REDUCED)
    # the rehearsal's sizes are the same family's
    tiny = dict(config, **config.get("rehearsal", {}))
    assert family.sizes(tiny)["L"] >= 1


CUT = {"n_routed_experts": "moe.num_experts", "vocab_size": "vocab_size",
       "num_hidden_layers": "num_layers"}
SOUND = {"reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
         "num_hidden_layers": 8, "source_num_hidden_layers": 61,
         "n_routed_experts": 12, "source_n_routed_experts": 192,
         "vocab_size": 20480, "source_vocab_size": 163840,
         "chips_sharing_a_layer": 16}


@pytest.mark.parametrize("change,fault", [
    ({}, None),
    ({"reduced": ["num_hidden_layers"], "chips_sharing_a_layer": None}, None),
    ({"reduced": ["q_lora_rank"], "q_lora_rank": 64}, "not a key the family can cut"),
    ({"source_n_routed_experts": None}, "no published count"),
    ({"source_vocab_size": 1024}, "no published count"),     # under what is held
    ({"chips_sharing_a_layer": None}, "states no chips_sharing_a_layer"),
    ({"chips_sharing_a_layer": 8}, "do not hold the published 192"),
    ({"n_routed_experts": 6, "chips_sharing_a_layer": 32}, "under 8 routed"),
    ({"vocab_size": 20479}, "under an eighth"),
])
def test_a_cut_states_its_source_and_deployment_and_keeps_the_floors(change, fault):
    config = {k: v for k, v in {**SOUND, **change}.items() if v is not None}
    faults = cut_faults(config, CUT)
    if fault is None:
        assert not faults
    else:
        assert len(faults) >= 1 and any(fault in x for x in faults), faults
