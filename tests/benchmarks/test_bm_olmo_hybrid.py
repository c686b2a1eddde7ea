"""The Olmo-Hybrid family as the harness has it (``families/olmo_hybrid.py``,
``configs/olmo-hybrid-7b-int8.json``): the file is the catalog's config and
the preset, whole; its bytes by hand; the leaves handed to the reference are
the served tree's once the engine has stacked the runs by period; reference
and program agree at the rehearsal size, and the int4 control does not; the
rehearsal of its cell end to end, sound, broken and with the control.
"""

import json
import os

import numpy as np
import pytest

from benchmarks import bytes_model, check, server
from benchmarks import weights as W
from benchmarks.loading import FAMILY_ANSWERS, load_data, load_family, load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FILE = os.path.join(ROOT, "benchmarks", "configs", "olmo-hybrid-7b-int8.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "olmo-hybrid-7b.log-turns"


def full() -> dict:
    with open(FILE) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny() -> dict:
    return load_data(FILE, rehearse=True)


def test_the_family_answers_everything_the_harness_asks():
    family = load_family(full())
    assert all(hasattr(family, name) for name in FAMILY_ANSWERS)
    assert family.REDUCED == {}, "nothing of this model may be cut"
    assert set(family.SCOPES) == {"lin_scan", "lin_proj", "state_io"}
    assert callable(family.lin_scan_floor_bytes)
    assert load_module("reference", "olmo_hybrid").layer


def test_the_file_as_committed_is_the_preset_whole():
    config = full()
    mc = server.model_config(config)
    server.check_against_preset(config, mc)
    assert config["reduced"] == []
    assert mc.mixer_period == ("linear", "linear", "linear", "attn")
    assert (mc.num_layers, mc.count_mixers("linear")) == (32, 24)
    assert (mc.linear_attn.decay, mc.linear_attn.gates) == ("head", "full")
    assert mc.post_norm and mc.qk_norm_whole and not mc.use_rope
    assert 7.42e9 < mc.num_params() < 7.44e9


@pytest.mark.parametrize("change,said", [
    ({"num_hidden_layers": 8}, "num_layers"),               # no depth cut
    ({"linear_value_head_dim": 128}, "linear_attn.value_head_dim"),
    ({"linear_key_head_dim": 128}, "linear_attn.key_head_dim"),
    ({"num_key_value_heads": 6}, "num_kv_heads"),
    ({"vocab_size": 12544}, "vocab_size"),
    ({"linear_allow_neg_eigval": False}, "linear_attn.neg_eigval"),
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
                   if '"Olmo-Hybrid-7B"' in line)
    config = full()
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert config[key] == value, key
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == config["name"])
    assert entry["source"] == row["source_url"] and entry["reduced"] == []


def test_the_bytes_by_hand():
    config = full()
    family = load_family(config)
    d, f, v = 3840, 11008, 100352
    # a sequence's state in one linear layer: 30 x 96 x 192 float32 and a
    # conv tail of 3 x (2 x 2880 + 5760) bfloat16
    row = 30 * 96 * 192 * 4 + 3 * 11520 * 2
    assert family.state_row_bytes(config) == row == 2_280_960
    assert family.lin_scan_floor_bytes(config, 16) == 2 * 16 * row * 24
    # pages: eight attention layers' keys and values, 30 heads of 128
    assert family.kv_token_bytes(config) == 2 * 8 * 3840 * 2 == 122_880
    mlp = 3 * d * f + 4 * (2 * f + d) + 2 * d * 2
    attn = 4 * d * d + 4 * 4 * d + 2 * d * 2
    linear = (2 * d * 2880 + 3 * d * 5760 + 2 * d * 30
              + 4 * (2 * 2880 + 5760 + d + 2 * 30 + 5760)
              + 2 * (4 * 11520 + 192) + 4 * 2 * 30)
    head = d * v + 4 * v + 2 * d
    assert family.weight_bytes(config) == (
        8 * attn + 24 * linear + 32 * mlp + head)
    assert 7.0e9 < family.weight_bytes(config) < 7.1e9     # less the embedding
    assert family.step_floor_bytes(config, 1000.0, 16.0) == (
        family.weight_bytes(config) + 1000 * 122_880 + 2 * 16 * row * 24
        + 16 * d * 2)
    assert bytes_model.step_floor_bytes(config, 1000.0, 16.0) == (
        family.step_floor_bytes(config, 1000.0, 16.0))


def test_the_references_leaves_are_the_engines_tree_by_period(tiny):
    """``stacks`` names the runs in the model's order, three linear layers
    and then one full one a period; the engine stacks them by period."""
    import jax
    from jax.sharding import PartitionSpec

    from opsagent_tpu.models import llama
    from opsagent_tpu.models.quant import quantize_specs

    family = load_family(tiny)
    sz, root = family.sizes(tiny), W.root_key(2**31 + 9)
    assert [s[0] for s in family.stacks(sz)] == [
        "layers:0:r0_linear", "layers:0:r1_attn",
        "layers:1:r0_linear", "layers:1:r1_attn"]
    assert [s[1:] for s in family.stacks(sz)] == [
        ("linear", 0, 3), ("full", 3, 1), ("linear", 4, 3), ("full", 7, 1)]
    mc = family.model_config(tiny)
    tree = llama.stack_layer_runs(mc, server.program_tree(tiny, 2**31 + 9))
    assert set(tree) == {"layers", "embed", "final_norm", "lm_head"}
    for _key, kind, first, count in family.stacks(sz):
        run = tree["layers"]["r1_attn" if kind == "full" else "r0_linear"]
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
    leaves = family.layer_leaves(root, "linear", 1, sz)
    assert leaves["wa"][0].shape == (64, 4) and leaves["wog"][0].shape == (64, 96)
    assert leaves["a_log"].shape == leaves["dt_bias"].shape == (4,)
    assert leaves["a_log"].dtype == leaves["dt_bias"].dtype == np.float32
    assert "wq" not in leaves and "f_down" not in leaves
    assert family.layer_leaves(root, "full", 3, sz)["qn"].shape == (64,)


def test_the_new_readers_give_nothing_where_the_program_counts_nothing():
    """A parent's program has neither the scopes nor the counters: a reader
    returns None and does not raise."""
    qwen = load_data(os.path.join(
        ROOT, "benchmarks", "configs", "qwen25-7b-int8.json"))
    ctx = {"before": {}, "after": {}, "trace": None, "config": qwen,
           "device": {"kind": "TPU v5 lite"}}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        added = [m for m in json.load(f)["per_layer"]
                 if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in added) == [
        "kernels.gdn_proj_ms", "kernels.gdn_scan_hbm_share",
        "kernels.gdn_scan_ms", "kernels.gdn_state_io_ms",
        "state.gdn_snapshot_hit_share"]
    assert all(m["moves"] == "tpot_p50_ms" for m in added)
    for config in (qwen, full()):
        ctx["config"] = config
        for m in added:
            assert load_module("layer_metrics", m["name"]).read(ctx) is None


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


# -- the cell's rehearsal, end to end ---------------------------------------------
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

    return copy_of_the_benchmark(tmp_path_factory.mktemp("olmo"))


def test_the_cell_is_the_last_and_rehearses_with_its_control(copy):
    """Exit 3 and ``correct`` true on the sound path (state slots, a
    snapshot restore, the five new readers found), while the reference at
    int4 in the program's place is outside the limits."""
    from test_bm_rehearsal import cells, rehearse

    assert cells()[-1] == CELL
    rc, last, out = rehearse(copy, CELL, "--trace", "1", "--control-bits", "4")
    assert rc == 3 and last["correct"] is True, said(out)
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "state.gdn_snapshot_hit_share" in last["rehearsal"]["per_layer_seen"]
    setup = json.loads(next(
        x for x in out.splitlines() if "server set-up:" in x
    ).split("server set-up: ", 1)[1])
    impl = setup["impl"]
    assert impl["lin_decay"] == "head" and impl["state_dtype"] == "float32"
    # 6 linear layers of 4 x 12 x 24 float32: rows of 128, nothing padded
    assert impl["state_layout"] == [6, 9, 128]
    assert impl["state_slot_bytes"] == 6 * 4 * 12 * 24 * 4
    line = next(x for x in out.splitlines() if "reference check:" in x)
    numbers = json.loads(line.split("reference check: ", 1)[1])
    assert numbers["checked_tokens"] >= 40
    limits = load_data(FILE, rehearse=True)["check"]["limits"]
    assert numbers["control"]["gap_max"] > limits["gap_max"] > numbers["gap_max"]
    assert "engine.state_copy" in out, "a snapshot was restored in the window"


def test_the_cells_broken_path_comes_out_not_correct(copy):
    from test_bm_rehearsal import rehearse

    rc, last, out = rehearse(copy, CELL, "--break-every", "9")
    assert rc == 3 and last["correct"] is False, said(out)
    assert "NOT MET" in out
