"""The GLM-4.7-Flash family as the harness has it (``families/
glm4_moe_lite.py``, ``configs/glm47-flash-l12-int8.json``): the file is the
catalog's config and the preset, cut in depth alone; its bytes by hand; the
leaves handed to the reference are the served tree's; reference and program
agree at the rehearsal size, and the int4 control does not; the eight new
readers; the rehearsal of its cell end to end in a COPY of the benchmark
(sound with the control, broken, and with the program's int8 latent pages).

Last, the control rehearsal of ``olmo-hybrid-7b.log-turns``, which
``test_bm_olmo_hybrid.py`` runs only where that cell is the benchmark's
last: it no longer is, that file is the benchmark's and not this PR's to
edit, so what its one test covered is covered here.
"""

import json
import os

import numpy as np
import pytest

from benchmarks import bytes_model, check, server
from benchmarks import weights as W
from benchmarks.loading import FAMILY_ANSWERS, load_data, load_family, load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FILE = os.path.join(ROOT, "benchmarks", "configs", "glm47-flash-l12-int8.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "glm47-flash-l12.longdoc-turns"
NEW = ["attn.live_context_share", "kernels.glm_moe_experts_hbm_share",
       "kernels.glm_moe_experts_ms", "kernels.glm_moe_router_ms",
       "kernels.mla_absorb_ms", "kernels.mla_attn_hbm_share",
       "kernels.mla_latent_ms", "moe.glm_tokens_per_expert"]


def full() -> dict:
    with open(FILE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny() -> dict:
    return load_data(FILE, rehearse=True)


def test_the_family_answers_everything_the_harness_asks():
    family = load_family(full())
    assert all(hasattr(family, name) for name in FAMILY_ANSWERS)
    assert family.REDUCED == {"num_hidden_layers": "num_layers"}
    assert set(family.SCOPES) == {
        "mla_absorb", "mla_latent", "moe_experts", "moe_router"}
    assert callable(family.mla_attn_floor_bytes)
    assert callable(family.moe_experts_floor_bytes)
    assert load_module("reference", "glm4_moe_lite").layer


def test_the_file_as_committed_is_the_preset_cut_in_depth_alone():
    config = full()
    mc = server.model_config(config)
    server.check_against_preset(config, mc)
    assert config["reduced"] == ["num_hidden_layers"]
    assert (config["source_num_hidden_layers"], mc.num_layers) == (47, 12)
    assert config["chips_sharing_a_layer"] == 1
    # floors: four layers and more after the dense one, every expert, the
    # whole vocabulary
    assert mc.num_layers - mc.moe_layer_start == 11 and mc.moe_layer_start == 1
    assert (mc.moe.num_experts, mc.moe.router_experts, mc.vocab_size) == (
        64, 64, 154880)
    assert mc.mla.latent_cache and mc.mla.v_head_dim == mc.head_dim_ == 256
    assert (mc.mla.latent_dim, mc.mla.page_dim) == (576, 640)
    assert "num_nextn_predict_layers" in config["not_served"]
    assert "pipeline stages" in config["deployment"]


@pytest.mark.parametrize("change,said", [
    ({"n_routed_experts": 8}, "moe.num_experts"),
    ({"vocab_size": 19360}, "vocab_size"),
    ({"v_head_dim": 128}, "mla.v_head_dim"),
    ({"kv_lora_rank": 256}, "mla.kv_lora_rank"),
    ({"num_experts_per_tok": 2}, "moe.num_experts_per_token"),
    ({"routed_scaling_factor": 1.0}, "moe.routed_scaling_factor"),
    ({"first_k_dense_replace": 0}, "moe_layer_start"),
    ({"reduced": ["num_hidden_layers", "n_routed_experts"]}, "cannot cut"),
])
def test_nothing_but_the_depth_may_differ_from_the_preset(change, said):
    config = dict(full(), **change)
    with pytest.raises(SystemExit, match=said):
        server.check_against_preset(config, server.model_config(config))


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalogs_config_is_in_the_file():
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"GLM-4.7-Flash"' in line)
    config = full()
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key != "num_hidden_layers":
            assert config[key] == value, key
    assert row["config"]["num_hidden_layers"] == config[
        "source_num_hidden_layers"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["configs"][-1]
    assert entry["name"] == config["name"]
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers"]
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, config["name"], "longdoc-turns", 1)


def test_the_traffic_is_the_issues():
    traffic = load_data(os.path.join(
        ROOT, "benchmarks", "traffic", "longdoc-turns.json"))
    assert (traffic["generator"], traffic["sessions"], traffic["turns"]) == (
        "closed_sessions", 16, 4)
    assert traffic["system_tokens"] == 1024 and not traffic["response_format"]
    assert traffic["first_user_tokens"] == {
        "dist": "lognormal", "median": 8192, "sigma": 0.5,
        "lo": 4096, "hi": 16384}
    assert traffic["observation_tokens"] == {
        "dist": "lognormal", "median": 128, "sigma": 0.7, "lo": 64, "hi": 512}
    assert traffic["max_tokens"] == {"dist": "uniform", "lo": 64, "hi": 64}
    assert (traffic["think_s"], traffic["stagger_s"],
            traffic["planned_sessions"]) == (0.5, 0.25, 128)
    # the longest session and its template fit a row's pages
    engine = full()["engine"]
    longest = 1024 + 16384 + 4 * 64 + 3 * 512 + 2 + 2 * 9
    assert longest <= engine["max_pages_per_seq"] * 16
    assert 16 * longest > engine["num_pages"] * 16 > 16 * (1024 + 8192 + 2000)


def test_the_bytes_by_hand():
    config = full()
    family = load_family(config)
    d, f, fe, v, E = 2048, 10240, 1536, 154880, 64
    # a token's latent in one layer: (512 + 64) bfloat16; the pages hold it
    # on 640 lanes, the floor counts what the attention needs
    assert family.latent_token_bytes(config) == 1152
    assert family.kv_token_bytes(config) == 12 * 1152 == 13_824
    assert family.mla_attn_floor_bytes(config, 1000.0) == 1000 * 13_824
    expert = 3 * d * fe + 4 * (2 * fe + d)
    assert family.moe_experts_floor_bytes(config, 1) == expert == 9_457_664
    assert family.moe_layers(config) == 11
    attn = (d * 768 + 768 * 5120 + d * 512 + d * 64 + 512 * 20 * 448
            + 5120 * d + 4 * (768 + 5120 + 512 + 64 + 20 * 448 + d)
            + 2 * (d + 768 + 512 + d))
    dense = 3 * d * f + 4 * (2 * f + d)
    moe = expert + 4 * (d * E + E)              # the shared expert, the router
    head = d * v + 4 * v + 2 * d
    assert family.held_weight_bytes(config) == (
        12 * attn + dense + 11 * (moe + E * expert) + head)
    assert 7.35e9 < family.held_weight_bytes(config) < 7.45e9   # less the embedding
    # the floor: the routed experts ONE token reaches, a layer; a pass of
    # more tokens reads more, none reads less
    floor = 12 * attn + dense + 11 * (moe + 4 * expert) + head
    assert family.weight_bytes(config) == floor
    assert floor < 0.2 * family.held_weight_bytes(config)
    assert family.step_floor_bytes(config, 1000.0, 16.0) == (
        floor + 1000 * 13_824 + 16 * d * 2)
    assert bytes_model.step_floor_bytes(config, 1000.0, 16.0) == (
        family.step_floor_bytes(config, 1000.0, 16.0))


def test_the_references_leaves_are_the_engines_tree(tiny):
    import jax
    from jax.sharding import PartitionSpec

    from opsagent_tpu.models import llama
    from opsagent_tpu.models.quant import quantize_specs

    family = load_family(tiny)
    sz, root = family.sizes(tiny), W.root_key(2**31 + 9)
    assert family.stacks(sz) == (
        ("layers", "dense", 0, 1), ("moe_layers", "experts", 1, 2))
    mc = family.model_config(tiny)
    tree = server.program_tree(tiny, 2**31 + 9)
    assert set(tree) == {"layers", "moe_layers", "embed", "final_norm", "lm_head"}
    for key, kind, first, count in family.stacks(sz):
        for i in range(count):
            for name, leaf in family.layer_leaves(root, kind, first + i, sz).items():
                served = tree[key][name]
                if isinstance(leaf, tuple):
                    np.testing.assert_array_equal(served.q[i], leaf[0])
                    np.testing.assert_array_equal(
                        served.dequantize()[i], W.as_float32(leaf))
                else:
                    np.testing.assert_array_equal(served[i], leaf)
    specs = quantize_specs(llama.param_specs(mc), mode="int8")
    assert jax.tree.structure(tree) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
    leaves = family.layer_leaves(root, "experts", 1, sz)
    assert leaves["eg"][0].shape == (8, 64, 32)
    assert leaves["wukv"][0].shape == (32, 4 * (16 + 24))
    assert leaves["wo"][0].shape == (4 * 24, 64)        # value as wide as query
    assert leaves["router"].dtype == leaves["router_bias"].dtype == np.float32
    assert float(abs(leaves["router_bias"]).max()) > 0  # seeded, not zero


def test_the_new_readers_give_nothing_where_the_program_counts_nothing():
    """A parent's program has neither the scopes nor the counters: a reader
    returns None and does not raise."""
    qwen = load_data(os.path.join(
        ROOT, "benchmarks", "configs", "qwen25-7b-int8.json"))
    ctx = {"before": {}, "after": {}, "trace": None, "config": qwen,
           "device": {"kind": "TPU v5 lite"}}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    added = bench["per_layer"][-8:]
    assert sorted(m["name"] for m in added) == NEW
    assert all(m["moves"] == "tpot_p50_ms" and m["workloads"] == [CELL]
               for m in added)
    layers = {m["layer"] for m in bench["per_layer"][:-8]}
    assert {m["layer"] for m in added} <= layers
    for config in (qwen, full()):
        ctx["config"] = config
        for m in added:
            assert load_module("layer_metrics", m["name"]).read(ctx) is None


def test_the_shares_read_their_counters_and_stay_under_their_floors(monkeypatch):
    """The two byte shares from a scrape as the program writes it: the
    expert share times ELEVEN layers (its Solar twin times every layer
    would read a twelfth too high), the attention share from live context
    tokens a pass with a fused block counted as its eight passes."""
    from benchmarks import scope_reduce

    config = full()
    family = load_family(config)

    def scrape(live, read, mixed, blocks, touched, passes):
        return {
            "opsagent_attn_context_tokens_total": [
                ({"what": "live"}, live), ({"what": "read"}, read)],
            "opsagent_decode_dispatches_total": [
                ({"kind": "mixed_async"}, mixed), ({"kind": "block"}, blocks)],
            "opsagent_moe_share_total": [
                ({"what": "experts_touched"}, touched),
                ({"what": "moe_layer_passes"}, passes),
                ({"what": "landed"}, 64.0 * passes)],
        }

    before = scrape(0, 0, 0, 0, 0, 0)
    after = scrape(160_000.0 * 180, 16 * 1216 * 16 * 180.0, 100, 10,
                   40.0 * 1980, 1980.0)
    ctx = {"before": before, "after": after, "config": config,
           "device": {"kind": "TPU v5 lite"}, "trace": {"devices": 1}}
    ms = {"attn_core": 20.0, "kv_gather": 10.0, "moe_experts": 8.0}
    monkeypatch.setattr(
        scope_reduce, "scope_ms_per_pass",
        lambda ctx, *scopes: sum(ms[s] for s in scopes))
    read = lambda name: load_module("layer_metrics", name).read(ctx)  # noqa: E731
    assert read("attn.live_context_share") == pytest.approx(
        100 * 160_000 / (16 * 1216 * 16))
    assert read("moe.glm_tokens_per_expert") == pytest.approx(1.0)
    attn = read("kernels.mla_attn_hbm_share")
    assert attn == pytest.approx(
        100 * (160_000 * 13_824 / 819e9) / 30e-3, rel=1e-3)
    experts = read("kernels.glm_moe_experts_hbm_share")
    assert experts == pytest.approx(
        100 * (11 * 40 * 9_457_664 / 819e9) / 8e-3, rel=1e-3)
    assert 0 < attn < 100 and 0 < experts < 100
    assert family.moe_layers(config) * 12 == 11 * config["num_hidden_layers"]


# -- the reference against the engine at the rehearsal size ----------------------
@pytest.mark.parametrize("seed", (3, 2**31 + 77))
def test_engine_tokens_sit_on_the_reference_and_the_control_does_not(tiny, seed):
    """Chunked prefill, mixed steps and fused decode blocks over latent
    pages, the expert layers as a share, served as ``test_bm_reference.py``
    serves the Qwen2 family."""
    from test_bm_reference import serve

    limits = tiny["check"]["limits"]
    numbers = check.run_check(tiny, seed, serve(tiny, seed), control_bits=4)
    assert numbers["checked_tokens"] >= 40
    ok, lines = check.verdict(numbers, limits)
    assert ok, lines
    assert numbers["agree_share"] == 1.0
    control = dict(numbers, **numbers["control"])
    assert not check.verdict(control, limits)[0]
    assert numbers["control"]["gap_max"] > 3 * max(
        numbers["gap_max"], limits["gap_max"])


# -- the cell's rehearsal, end to end, in a copy ------------------------------------
def said(out: str) -> str:
    """What ``run.py`` itself printed, without the server's log."""
    return "\n".join(x[:400] for x in out.splitlines() if x.startswith("[bench]"))


@pytest.fixture(scope="module")
def copy(tmp_path_factory) -> str:
    """A copy of the benchmark to rehearse in: ``run.py`` keeps a cell's
    server log, trace and flight directory under its own root, where
    ``test_bm_rehearsal.py`` may be rehearsing the same cell in another
    worker at the same time."""
    from test_bm_rehearsal import copy_of_the_benchmark

    return copy_of_the_benchmark(tmp_path_factory.mktemp("glm"))


def test_the_cell_is_the_last_and_rehearses_with_its_control(copy):
    from test_bm_rehearsal import cells, rehearse

    assert cells()[-1] == CELL
    rc, last, out = rehearse(copy, CELL, "--trace", "1", "--control-bits", "4")
    assert rc == 3 and last["correct"] is True, said(out)
    assert last["attempted"] > 0 and last["failed"] == 0
    seen = last["rehearsal"]["per_layer_seen"]
    # the counters' readers find something on the CPU; the device trace's do not
    assert {"attn.live_context_share", "moe.glm_tokens_per_expert"} <= set(seen)
    assert set(last["rehearsal"]["end_to_end_seen"]) == {"setup_s", "tpot_p50_ms"}
    setup = json.loads(next(
        x for x in out.splitlines() if "server set-up:" in x
    ).split("server set-up: ", 1)[1])
    impl = setup["impl"]
    assert (impl["attn_impl"], impl["kv_page_form"], impl["kv_quantize"]) == (
        "xla", "merged", "none")
    line = next(x for x in out.splitlines() if "reference check:" in x)
    numbers = json.loads(line.split("reference check: ", 1)[1])
    assert numbers["checked_tokens"] >= 40
    limits = load_data(FILE, rehearse=True)["check"]["limits"]
    assert numbers["control"]["gap_max"] > limits["gap_max"] > numbers["gap_max"]


def test_the_cells_broken_path_comes_out_not_correct(copy):
    from test_bm_rehearsal import rehearse

    rc, last, out = rehearse(copy, CELL, "--break-every", "9")
    assert rc == 3 and last["correct"] is False, said(out)
    assert "NOT MET" in out


def test_the_cells_int8_latent_pages_run_as_a_control(copy):
    from test_bm_rehearsal import rehearse

    rc, last, out = rehearse(copy, CELL, "--engine", "kv_quantize=int8")
    assert rc == 3 and last["correct"] is False, said(out)
    assert "CONTROL RUN" in out and '"kv_quantize": "int8"' in out
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "compared precision_mismatches: 1 (limit <= 0) NOT MET" in out


def test_olmo_hybrids_cell_still_rehearses_with_its_control(copy):
    """What ``test_bm_olmo_hybrid.py::test_the_cell_is_the_last_and_
    rehearses_with_its_control`` ran until a cell was appended behind its
    own (its first line asserts that its cell is the last)."""
    from test_bm_rehearsal import cells, rehearse

    cell = "olmo-hybrid-7b.log-turns"
    assert cell in cells()[:-1]
    rc, last, out = rehearse(copy, cell, "--trace", "1", "--control-bits", "4")
    assert rc == 3 and last["correct"] is True, said(out)
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "state.gdn_snapshot_hit_share" in last["rehearsal"]["per_layer_seen"]
    setup = json.loads(next(
        x for x in out.splitlines() if "server set-up:" in x
    ).split("server set-up: ", 1)[1])
    impl = setup["impl"]
    assert impl["lin_decay"] == "head" and impl["state_dtype"] == "float32"
    assert impl["state_layout"] == [6, 9, 128]
    assert impl["state_slot_bytes"] == 6 * 4 * 12 * 24 * 4
    line = next(x for x in out.splitlines() if "reference check:" in x)
    numbers = json.loads(line.split("reference check: ", 1)[1])
    assert numbers["checked_tokens"] >= 40
    limits = load_data(os.path.join(
        ROOT, "benchmarks", "configs", "olmo-hybrid-7b-int8.json"),
        rehearse=True)["check"]["limits"]
    assert numbers["control"]["gap_max"] > limits["gap_max"] > numbers["gap_max"]
    assert "engine.state_copy" in out, "a snapshot was restored in the window"
