"""The dense Jamba family as the harness has it (``families/jamba.py``,
``configs/jamba2-3b-int8.json``, ``traffic/chat-fanout.json``): the file is
the catalog's config and the preset, whole; the traffic is ISSUE 42's, number
for number; its bytes by hand; the leaves handed to the reference are the
served tree's once the engine has stacked the runs by period; reference and
program agree at the rehearsal size, and the int4 control does not; the new
readers; the rehearsal of its cell end to end in a copy of the benchmark,
sound, broken, with the control and with int8 pages.

Every entry is found BY NAME, never by position, so that the next
configuration appended behind this one loses nothing here. What
``test_bm_glm4_moe_lite.py`` asserted by position until this cell was
appended behind its own (GLM's cell rehearsed with its int4 control, GLM's
entries in ``BENCHMARK.json``, GLM's eight readers) is asked here by name
too.
"""

import json
import os

import numpy as np
import pytest

from benchmarks import bytes_model, check, server
from benchmarks import weights as W
from benchmarks.loading import FAMILY_ANSWERS, load_data, load_family, load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FILE = os.path.join(ROOT, "benchmarks", "configs", "jamba2-3b-int8.json")
TRAFFIC = os.path.join(ROOT, "benchmarks", "traffic", "chat-fanout.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "jamba2-3b.chat-fanout"
GLM_CELL = "glm47-flash-l12.longdoc-turns"
NEW = ["kernels.ssm_proj_ms", "kernels.ssm_scan_hbm_share",
       "kernels.ssm_scan_ms", "kernels.ssm_state_io_ms",
       "ssm.scan_fill_share", "state.ssm_snapshot_hit_share"]


def full() -> dict:
    with open(FILE) as f:
        return json.load(f)


def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def named(entries: list, name: str) -> dict:
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


@pytest.fixture(scope="module")
def tiny() -> dict:
    return load_data(FILE, rehearse=True)


def test_the_family_answers_everything_the_harness_asks():
    family = load_family(full())
    assert all(hasattr(family, name) for name in FAMILY_ANSWERS)
    assert family.REDUCED == {}, "nothing of this model may be cut"
    assert set(family.SCOPES) == {"ssm_scan", "ssm_proj", "state_io"}
    assert callable(family.ssm_scan_floor_bytes)
    ref = load_module("reference", "jamba")
    assert ref.layer and ref.logits and ref.mamba_mixer


def test_the_file_as_committed_is_the_preset_whole_but_for_the_head():
    """Every published key as published, the tied head among them; the
    program's model is the preset with a head of its own, which the
    harness's draw of ``embed`` and ``lm_head`` forces (``assumed``)."""
    from opsagent_tpu.models.config import get_config_preset

    config = full()
    mc = server.model_config(config)
    server.check_against_preset(config, mc)
    assert config["reduced"] == [] and config["preset"] == "jamba2-3b-untied"
    assert config["tie_word_embeddings"] is True and not mc.tie_embeddings
    published = get_config_preset("jamba2-3b")
    assert published.tie_embeddings
    assert published.num_params() == 3_029_337_472
    assert mc.num_params() - published.num_params() == 2560 * 65536
    assert mc.mixer_period == ("mamba",) * 7 + ("attn",) + ("mamba",) * 6
    assert (mc.num_layers, mc.count_mixers("mamba")) == (28, 26)
    assert (mc.num_kv_heads, mc.head_dim_, mc.use_rope) == (1, 128, False)
    said = " ".join(config["assumed"])
    for what in ("attn_layer_period", "head_dim 128", "UNTIED"):
        assert what in said, what


@pytest.mark.parametrize("change,said", [
    ({"num_hidden_layers": 14}, "num_layers"),              # no depth cut
    ({"mamba_d_state": 8}, "mamba.d_state"),
    ({"mamba_expand": 1}, "mamba.d_inner"),
    ({"mamba_dt_rank": 80}, "mamba.dt_rank"),
    ({"num_key_value_heads": 4}, "num_kv_heads"),
    ({"vocab_size": 8192}, "vocab_size"),
    ({"attn_layer_offset": 0}, "mixer_period"),
    ({"preset": "jamba2-3b"}, "tie_embeddings"),            # the tied preset
    ({"reduced": ["num_hidden_layers"]}, "cannot cut"),
])
def test_nothing_may_differ_from_the_preset(change, said):
    config = dict(full(), **change)
    with pytest.raises(SystemExit, match=said):
        server.check_against_preset(config, server.model_config(config))


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_key_of_the_catalogs_config_is_in_the_file():
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"AI21-Jamba2-3B"' in line)
    config = full()
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert config[key] == value, key
    entry = named(bench()["configs"], config["name"])
    assert entry["source"] == row["source_url"] and entry["reduced"] == []
    assert entry["file"] == "benchmarks/configs/jamba2-3b-int8.json"
    cell = named(bench()["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        config["name"], "chat-fanout", 1)


def test_glms_entries_are_where_they_were_by_name():
    """What ``test_bm_glm4_moe_lite.py`` asked of ``configs[-1]`` and
    ``workloads[-1]``."""
    entry = named(bench()["configs"], "glm47-flash-l12-int8")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"].startswith("https://huggingface.co/zai-org/GLM-4.7")
    cell = named(bench()["workloads"], GLM_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm47-flash-l12-int8", "longdoc-turns", 1)


def test_the_traffic_is_the_issues_number_for_number():
    with open(TRAFFIC) as f:
        t = json.load(f)
    assert (t["generator"], t["loop"]) == ("closed_sessions", "closed")
    assert (t["sessions"], t["turns"], t["system_tokens"]) == (96, 3, 1024)
    assert t["first_user_tokens"] == {
        "dist": "lognormal", "median": 192, "sigma": 0.6, "lo": 64, "hi": 768}
    assert t["observation_tokens"] == {
        "dist": "lognormal", "median": 96, "sigma": 0.7, "lo": 32, "hi": 384}
    assert t["max_tokens"] == {"dist": "uniform", "lo": 64, "hi": 64}
    assert (t["think_s"], t["stagger_s"], t["planned_sessions"]) == (
        0.5, 0.1, 960)
    assert t["response_format"] is None
    # the longest session and its template stay inside a sequence's pages
    engine = full()["engine"]
    assert 1024 + 768 + 3 * 64 + 2 * 384 < 3072 == 16 * engine["max_pages_per_seq"]
    assert engine["max_batch_size"] == 64 < t["sessions"]


def test_the_bytes_by_hand():
    config = full()
    family = load_family(config)
    d, f, v, di = 2560, 8192, 65536, 5120
    # a sequence's state in one Mamba layer: 16 x 5120 float32 and a conv
    # tail of 3 x 5120 bfloat16
    row = 16 * di * 4 + 3 * di * 2
    assert family.state_row_bytes(config) == row == 358_400
    assert 26 * row == 9_318_400
    assert family.ssm_scan_floor_bytes(config, 64) == 2 * 64 * row * 26
    # pages: two attention layers' keys and values, one kv head of 128
    assert family.kv_token_bytes(config) == 2 * 2 * 128 * 2 == 1024
    mlp = 3 * d * f + 4 * (2 * f + d) + 2 * d * 2
    attn = 2 * d * d + 2 * d * 128 + 4 * (2 * d + 2 * 128)
    assert 2 * d * d + 2 * d * 128 == 13_762_560
    matrices = d * 2 * di + di * 192 + 160 * di + di * d
    assert matrices == 41_123_840
    mamba = (matrices + 4 * (2 * di + 192 + di + d)
             + 2 * (5 * di + 192) + 4 * (16 * di + 2 * di))
    head = d * v + 4 * v + 2 * d
    assert family.weight_bytes(config) == 2 * attn + 26 * mamba + 28 * mlp + head
    assert 3.02e9 < family.weight_bytes(config) < 3.06e9   # less the embedding
    assert family.step_floor_bytes(config, 1000.0, 64.0) == (
        family.weight_bytes(config) + 1000 * 1024 + 2 * 64 * row * 26
        + 64 * d * 2)
    assert bytes_model.step_floor_bytes(config, 1000.0, 64.0) == (
        family.step_floor_bytes(config, 1000.0, 64.0))


def test_the_references_leaves_are_the_engines_tree_by_period(tiny):
    """``stacks`` names the runs in the model's order, two Mamba layers,
    the attention layer and one more Mamba layer a period at the rehearsal
    size; the engine stacks them by period."""
    import jax
    from jax.sharding import PartitionSpec

    from opsagent_tpu.models import llama
    from opsagent_tpu.models.quant import quantize_specs

    family = load_family(tiny)
    sz, root = family.sizes(tiny), W.root_key(2**31 + 9)
    assert [s[0] for s in family.stacks(sz)] == [
        f"layers:{p}:{run}" for p in (0, 1)
        for run in ("r0_mamba", "r1_attn", "r2_mamba")]
    assert [s[1:] for s in family.stacks(sz)] == [
        ("mamba", 0, 2), ("attention", 2, 1), ("mamba", 3, 1),
        ("mamba", 4, 2), ("attention", 6, 1), ("mamba", 7, 1)]
    whole = family.sizes(full())
    assert [s[1:] for s in family.stacks(whole)][:3] == [
        ("mamba", 0, 7), ("attention", 7, 1), ("mamba", 8, 6)]
    mc = family.model_config(tiny)
    tree = llama.stack_layer_runs(mc, server.program_tree(tiny, 2**31 + 9))
    assert set(tree) == {"layers", "embed", "final_norm", "lm_head"}
    for key, kind, first, count in family.stacks(sz):
        run = tree["layers"][key.split(":")[2]]
        for i in range(count):
            for name, leaf in family.layer_leaves(root, kind, first + i, sz).items():
                served = run[name]
                if isinstance(leaf, tuple):
                    np.testing.assert_array_equal(served.q[first // 4, i], leaf[0])
                    np.testing.assert_array_equal(
                        served.dequantize()[first // 4, i], W.as_float32(leaf))
                else:
                    np.testing.assert_array_equal(served[first // 4, i], leaf)
    specs = quantize_specs(llama.param_specs(mc), mode="int8")
    assert jax.tree.structure(tree) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
    leaves = family.layer_leaves(root, "mamba", 1, sz)
    assert leaves["m_in"][0].shape == (64, 256)
    assert leaves["m_x"][0].shape == (128, 8 + 32)
    assert leaves["a_log"].shape == (16, 128), "[d_state, d_inner]"
    assert leaves["dt_bias"].shape == leaves["d_skip"].shape == (128,)
    assert {leaves[n].dtype for n in ("a_log", "dt_bias", "d_skip")} == {
        np.dtype("float32")}
    # A = -exp(A_log) near -(1..16) along the state axis, steps of 0.001..0.1
    assert np.allclose(np.exp(leaves["a_log"]).mean(axis=1),
                       np.arange(1, 17), rtol=0.1)
    steps = np.log1p(np.exp(np.asarray(leaves["dt_bias"], np.float64)))
    assert 0.00099 < steps.min() and steps.max() < 0.101
    assert "wq" not in leaves
    assert family.layer_leaves(root, "attention", 2, sz)["wk"][0].shape == (64, 16)


def test_the_new_readers_give_nothing_where_the_program_counts_nothing():
    """A parent's program has neither the scopes nor the counters: a reader
    returns None and does not raise."""
    qwen = load_data(os.path.join(
        ROOT, "benchmarks", "configs", "qwen25-7b-int8.json"))
    ctx = {"before": {}, "after": {}, "trace": None, "config": qwen,
           "device": {"kind": "TPU v5 lite"}}
    added = [m for m in bench()["per_layer"] if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in added) == NEW
    assert all(m["moves"] == "tpot_p50_ms" for m in added)
    assert {m["name"]: m["layer"] for m in added} == {
        **{n: "kernels" for n in NEW[:4]}, "ssm.scan_fill_share": "model step",
        "state.ssm_snapshot_hit_share": "prefix trie and pages"}
    for config in (qwen, full()):
        ctx["config"] = config
        for m in added:
            assert load_module("layer_metrics", m["name"]).read(ctx) is None


def test_glms_readers_still_give_nothing_where_the_program_counts_nothing():
    """What ``test_bm_glm4_moe_lite.py`` asked of ``per_layer[-8:]`` until
    six entries were appended behind GLM's eight: found by their cell."""
    qwen = load_data(os.path.join(
        ROOT, "benchmarks", "configs", "qwen25-7b-int8.json"))
    glm = load_data(os.path.join(
        ROOT, "benchmarks", "configs", "glm47-flash-l12-int8.json"))
    ctx = {"before": {}, "after": {}, "trace": None, "config": qwen,
           "device": {"kind": "TPU v5 lite"}}
    added = [m for m in bench()["per_layer"]
             if m.get("workloads") == [GLM_CELL]]
    assert sorted(m["name"] for m in added) == [
        "attn.live_context_share", "kernels.glm_moe_experts_hbm_share",
        "kernels.glm_moe_experts_ms", "kernels.glm_moe_router_ms",
        "kernels.mla_absorb_ms", "kernels.mla_attn_hbm_share",
        "kernels.mla_latent_ms", "moe.glm_tokens_per_expert"]
    assert all(m["moves"] == "tpot_p50_ms" for m in added)
    for config in (qwen, glm):
        ctx["config"] = config
        for m in added:
            assert load_module("layer_metrics", m["name"]).read(ctx) is None


def test_the_new_shares_read_at_most_their_whole(monkeypatch):
    """The fill share is real over computed steps; the scan's share of its
    roofline is 100% exactly where the scopes' time is the floor's own
    (2 x rows x 358,400 B x 26 at 819 GB/s), and under it at any longer
    time: prefill rows are not in the rows, so the floor is low."""
    from benchmarks import scope_reduce

    def counters(real, computed, lanes, dispatches):
        steps = "opsagent_ssm_scan_steps_total"
        name = "opsagent_mixed_dispatch_decode_lanes"
        return {steps: [({"kind": "real"}, real), ({"kind": "computed"}, computed)],
                name + "_sum": [({}, lanes)], name + "_count": [({}, dispatches)]}

    ctx = {"before": counters(0.0, 0.0, 0.0, 0.0), "config": full(),
           "after": counters(256.0 * 26, 1024.0 * 26, 64.0, 1.0),
           "trace": {"devices": 1}, "device": {"kind": "TPU v5 lite"}}
    fill = load_module("layer_metrics", "ssm.scan_fill_share")
    assert fill.read(ctx) == 25.0
    floor_ms = 2 * 64 * 358_400 * 26 / 819e9 * 1e3
    share = load_module("layer_metrics", "kernels.ssm_scan_hbm_share")
    for ms, want in ((floor_ms, 100.0), (4 * floor_ms, 25.0)):
        monkeypatch.setattr(
            scope_reduce, "scope_ms_per_pass", lambda ctx, *scopes, ms=ms: (
                ms if scopes == ("ssm_scan", "state_io") else None))
        assert share.read(ctx) == pytest.approx(want)
    ctx["config"] = load_data(os.path.join(
        ROOT, "benchmarks", "configs", "olmo-hybrid-7b-int8.json"))
    assert share.read(ctx) is None, "another family has no such floor"


# -- the reference against the engine at the rehearsal size ----------------------
@pytest.mark.parametrize("seed", (3, 2**31 + 77))
def test_engine_tokens_sit_on_the_reference_and_the_control_does_not(tiny, seed):
    """Chunked prefill, mixed steps, fused decode blocks and state slots,
    served as ``test_bm_reference.py`` serves the Qwen2 family."""
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


# -- the cells' rehearsals, end to end, in a copy ----------------------------------
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

    return copy_of_the_benchmark(tmp_path_factory.mktemp("jamba"))


def _setup_and_numbers(out: str) -> tuple[dict, dict]:
    setup = json.loads(next(
        x for x in out.splitlines() if "server set-up:" in x
    ).split("server set-up: ", 1)[1])
    line = next(x for x in out.splitlines() if "reference check:" in x)
    return setup, json.loads(line.split("reference check: ", 1)[1])


def test_the_cell_is_found_by_name_and_rehearses_with_its_control(copy):
    """Exit 3 and ``correct`` true on the sound path (state slots, a
    snapshot restore, the counters' readers found), while the reference at
    int4 in the program's place is outside the limits."""
    from test_bm_rehearsal import cells, rehearse

    assert CELL in cells()
    rc, last, out = rehearse(copy, CELL, "--trace", "1", "--control-bits", "4")
    assert rc == 3 and last["correct"] is True, said(out)
    assert last["attempted"] > 0 and last["failed"] == 0
    seen = set(last["rehearsal"]["per_layer_seen"])
    # the counters' readers find something on the CPU; the device trace's do not
    assert {"ssm.scan_fill_share", "state.ssm_snapshot_hit_share"} <= seen
    assert not seen & set(NEW[:4])
    assert set(last["rehearsal"]["end_to_end_seen"]) == {"setup_s", "tpot_p50_ms"}
    setup, numbers = _setup_and_numbers(out)
    impl = setup["impl"]
    assert (impl["state_mixer"], impl["state_impl"]) == ("mamba", "xla")
    assert impl["state_dtype"] == "float32" and "lin_decay" not in impl
    # 6 Mamba layers of 16 x 128 float32 and a flat tail of 3 x 128
    assert impl["state_layout"] == [6, 16, 128]
    assert impl["state_slot_bytes"] == 6 * 16 * 128 * 4
    assert impl["conv_slot_bytes"] == 6 * 3 * 128 * 4
    assert (impl["attn_impl"], impl["kv_quantize"]) == ("xla", "none")
    assert numbers["checked_tokens"] >= 40
    limits = load_data(FILE, rehearse=True)["check"]["limits"]
    assert numbers["control"]["gap_max"] > limits["gap_max"] > numbers["gap_max"]
    assert "engine.state_copy" in out, "a snapshot was restored in the window"


def test_the_cells_broken_path_comes_out_not_correct(copy):
    from test_bm_rehearsal import rehearse

    rc, last, out = rehearse(copy, CELL, "--break-every", "9")
    assert rc == 3 and last["correct"] is False, said(out)
    assert "NOT MET" in out


def test_the_cells_int8_pages_run_as_a_control(copy):
    """int8 pages run for this model (through the gather, at one kv head)
    and come out not correct by the program's own report."""
    from test_bm_rehearsal import rehearse

    rc, last, out = rehearse(copy, CELL, "--engine", "kv_quantize=int8")
    assert rc == 3 and last["correct"] is False, said(out)
    assert "CONTROL RUN" in out and '"kv_quantize": "int8"' in out
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "precision not as stated: kv_pages: stated float32" in out
    assert "compared precision_mismatches: 1 (limit <= 0) NOT MET" in out


def test_glms_cell_still_rehearses_with_its_control(copy):
    """What ``test_bm_glm4_moe_lite.py::test_the_cell_is_the_last_and_
    rehearses_with_its_control`` ran until a cell was appended behind its
    own (its first line asserts that its cell is the last)."""
    from test_bm_rehearsal import cells, rehearse

    assert GLM_CELL in cells()
    rc, last, out = rehearse(copy, GLM_CELL, "--trace", "1", "--control-bits", "4")
    assert rc == 3 and last["correct"] is True, said(out)
    assert last["attempted"] > 0 and last["failed"] == 0
    seen = last["rehearsal"]["per_layer_seen"]
    assert {"attn.live_context_share", "moe.glm_tokens_per_expert"} <= set(seen)
    assert set(last["rehearsal"]["end_to_end_seen"]) == {"setup_s", "tpot_p50_ms"}
    setup, numbers = _setup_and_numbers(out)
    impl = setup["impl"]
    assert (impl["attn_impl"], impl["kv_page_form"], impl["kv_quantize"]) == (
        "xla", "merged", "none")
    assert numbers["checked_tokens"] >= 40
    limits = load_data(os.path.join(
        ROOT, "benchmarks", "configs", "glm47-flash-l12-int8.json"),
        rehearse=True)["check"]["limits"]
    assert numbers["control"]["gap_max"] > limits["gap_max"] > numbers["gap_max"]
