"""The Solar-Open2 family as the harness has it (``families/solar_open2.py``,
``configs/solar-open2-ep8-l8-int8.json``): what ``test_bm_families.py`` asks
of "the last configuration" in the Qwen2 family's terms, asked of this one
in its own; its bytes by hand; and that the leaves handed to the reference
are the served tree's values once the engine has stacked the runs by period.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

from benchmarks import bytes_model, server
from benchmarks import weights as W
from benchmarks.loading import load_data, load_family

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FILE = os.path.join(ROOT, "benchmarks", "configs", "solar-open2-ep8-l8-int8.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def full() -> dict:
    with open(FILE) as f:
        return json.load(f)


def test_the_file_as_committed_equals_its_preset_but_for_the_three_cuts():
    config = full()
    mc = server.model_config(config)
    server.check_against_preset(config, mc)
    assert (mc.num_layers, mc.moe.num_experts, mc.vocab_size) == (8, 40, 24576)
    assert mc.moe.router_experts == 320 and mc.moe.first_expert == 0
    assert mc.mixer_period == ("attn", "linear", "linear", "linear")
    assert config["chips_sharing_a_layer"] * config["n_routed_experts"] == 320


@pytest.mark.parametrize("change,said", [
    ({"num_attention_heads": 32}, "num_heads"),                  # a width: never
    ({"moe_intermediate_size": 640}, "expert_intermediate_size"),
    ({"linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 64,
                             "num_heads": 64, "num_kv_heads": None}},
     "linear_attn.key_head_dim"),
    ({"source_n_routed_experts": 160}, "moe.router_experts"),    # the router's width
    ({"use_rope": True}, "use_rope"),
    ({"gqa_interval": 1}, "mixer_period"),
    ({"num_hidden_layers": 4, "reduced": ["n_routed_experts", "vocab_size"]},
     "num_layers"),                                              # not listed
    ({"reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size",
                  "head_dim"]}, "cannot cut"),
])
def test_only_the_three_cuts_may_differ_from_the_preset(change, said):
    config = dict(full(), **change)
    with pytest.raises(SystemExit, match=said):
        server.check_against_preset(config, server.model_config(config))


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_every_number_of_the_catalogs_config_is_in_the_file():
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"Solar-Open2-250B"' in line)
    config = full()
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["source_" + key] == value, key
        else:
            assert config[key] == value, key


def test_the_bytes_by_hand():
    config = full()
    family = load_family(config)
    d, fe = 4096, 1280
    one_expert = 3 * d * fe + 4 * (fe + fe + d)
    assert family.moe_experts_floor_bytes(config, 1) == one_expert
    # a sequence's state in one linear layer: 64 x 128 x 128 float32 and a
    # conv tail of 3 x 24576 bfloat16
    row = 64 * 128 * 128 * 4 + 3 * 24576 * 2
    assert family.state_row_bytes(config) == row
    assert family.lin_scan_floor_bytes(config, 32) == 2 * 32 * row * 6
    # pages: two attention layers' keys and values, 8 heads of 128, bfloat16
    assert family.kv_token_bytes(config) == 2 * 2 * 1024 * 2 == 8192
    gqa = 3 * d * 8192 + 2 * d * 1024 + 4 * (2 * 8192 + 2 * 1024 + d)
    linear = (3 * d * 8192 + 8192 * d + 2 * d * 128 + 2 * 128 * 8192 + d * 64
              + 4 * (5 * 8192 + d + 2 * 128 + 64)
              + 2 * (4 * 24576 + 128) + 4 * (64 + 8192))
    moe = 41 * one_expert + 4 * (d * 320 + 320) + 2 * d * 2
    head = d * 24576 + 4 * 24576 + 2 * d
    assert family.weight_bytes(config) == 2 * gqa + 6 * linear + 8 * moe + head
    assert 6.3e9 < family.weight_bytes(config) < 6.5e9    # less the embedding
    assert family.step_floor_bytes(config, 1000.0, 16.0) == (
        family.weight_bytes(config) + 1000 * 8192 + 2 * 32 * row * 6
        + 16 * d * 2)
    assert bytes_model.step_floor_bytes(config, 1000.0, 16.0) == (
        family.step_floor_bytes(config, 1000.0, 16.0))
    # fewer rows than experts reach fewer experts
    few = dict(config, engine=dict(config["engine"], max_batch_size=2))
    assert family.weight_bytes(config) - family.weight_bytes(few) == (
        8 * 24 * one_expert)


def test_the_references_leaves_are_the_engines_tree_by_period():
    """``stacks`` names the model's runs in order; the engine stacks them by
    period (``llama.stack_layer_runs``): layer ``l`` of the reference is
    ``[l // 4, (l % 4) - 1]`` of its run in the tree the layer scan walks."""
    from opsagent_tpu.models import llama

    tiny = load_data(FILE, rehearse=True)
    family = load_family(tiny)
    sz, root = family.sizes(tiny), W.root_key(2**31 + 9)
    names = [s[0] for s in family.stacks(sz)]
    assert names == ["moe_layers:0:r0_attn", "moe_layers:0:r1_linear"]
    assert [s[1:] for s in family.stacks(dict(sz, L=8))] == [
        ("gqa", 0, 1), ("linear", 1, 3), ("gqa", 4, 1), ("linear", 5, 3)]
    mc = family.model_config(tiny)
    tree = llama.stack_layer_runs(mc, server.program_tree(tiny, 2**31 + 9))
    assert set(tree) == {"moe_layers", "embed", "final_norm", "lm_head"}
    for _key, kind, first, count in family.stacks(sz):
        for i in range(count):
            run = tree["moe_layers"]["r0_attn" if kind == "gqa" else "r1_linear"]
            for name, leaf in family.layer_leaves(root, kind, first + i, sz).items():
                served = run[name]
                if isinstance(leaf, tuple):
                    np.testing.assert_array_equal(served.q[0, i], leaf[0])
                    np.testing.assert_array_equal(
                        served.dequantize()[0, i], W.as_float32(leaf))
                else:
                    np.testing.assert_array_equal(served[0, i], leaf)
    # and the tree has the structure the program's specs describe
    import jax
    from jax.sharding import PartitionSpec
    from opsagent_tpu.models.quant import quantize_specs

    specs = quantize_specs(llama.param_specs(mc), mode="int8")
    assert jax.tree.structure(tree) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
    leaves = family.layer_leaves(root, "linear", 1, sz)
    assert leaves["eg"][0].shape == (4, 64, 32)
    assert leaves["router"].shape == (64, 8), "the router's published width"
    assert leaves["a_log"].dtype == leaves["dt_bias"].dtype == np.float32
    assert "wq" not in leaves and "lq" in leaves


def test_the_new_readers_give_nothing_where_the_program_counts_nothing():
    """A parent's program has neither the scopes nor the counters: a reader
    returns None and does not raise."""
    from benchmarks.loading import load_module

    qwen = load_data(os.path.join(
        ROOT, "benchmarks", "configs", "qwen25-7b-int8.json"))
    ctx = {"before": {}, "after": {}, "trace": None, "config": qwen,
           "device": {"kind": "TPU v5 lite"}}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        added = [m["name"] for m in json.load(f)["per_layer"]
                 if m.get("workloads") == ["solar-open2-ep8-l8.doc-turns"]]
    assert len(added) == 9
    for name in added:
        assert load_module("layer_metrics", name).read(ctx) is None, name
    ctx["config"] = full()
    for name in added:
        assert load_module("layer_metrics", name).read(ctx) is None, name
