"""The seam by which a configuration's file names its model family.

For the two configurations the benchmark has, the tree that
``server.program_tree`` builds and the leaves handed to the reference are,
for three seeds, bit for bit what the harness built before the seam was
cut (digests recorded from commit b436722, before any edit): the same
``LEAF_NO`` numbering, key folding and draws. And the rule that only the
keys a family's ``REDUCED`` map allows may differ from the preset.
"""

import dataclasses
import hashlib
import json
import os

import numpy as np
import pytest

from benchmarks import bytes_model, server
from benchmarks import weights as W
from benchmarks.loading import FAMILY_ANSWERS, load_data, load_family

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEEDS = (3, 12345, 2**31 + 77)
# sha256 over (path, dtype, shape, bytes) of every leaf, from the parent's
# ``server.program_tree`` and ``weights.layer_leaves`` at the rehearsal
# size (the two configurations rehearse at the same sizes).
PARENT = {
    3: ("6cbdeb18abcab5b41c83edff5884d1aab34c3e848f875ac246788d9d25cf036f",
        "17b4b4dca18cec3fc0776d21bb779d5149acbb6cdc21f759ba6efbffec112fe8"),
    12345: ("7d89e9270d7a5b8e7c9ce8b4c7f4219cd0a7a1b4cfd58f26874597760f12832f",
            "58d0d8f0ae8d773c79eba30630595c0e6775401c78f6922f1612b2d438aec822"),
    2**31 + 77: (
        "9620f7af2298e9127cc62a6f780098202123cef0eb66057a78c9a79116554a7b",
        "abf1a45c4b9321a1537e13426dd21d335055c49f53410456bf32ac446515b982"),
}


def configs() -> list[str]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [c["file"] for c in json.load(f)["configs"]]


def tree_digest(tree) -> str:
    import jax

    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("file", configs())
def test_the_served_tree_and_the_references_leaves_are_the_parents(file, seed):
    tiny = load_data(os.path.join(ROOT, file), rehearse=True)
    family = load_family(tiny)
    assert tree_digest(server.program_tree(tiny, seed)) == PARENT[seed][0]
    sz, root = family.sizes(tiny), W.root_key(seed)
    handed = {}
    for _key, kind, first, count in family.stacks(sz):
        for layer in range(first, first + count):
            handed[str(layer)] = family.layer_leaves(root, kind, layer, sz)
    assert tree_digest(handed) == PARENT[seed][1]


def test_the_references_leaves_are_the_served_trees_values():
    """What ``check.run_check`` dequantizes for layer ``l`` is row ``l`` of
    the stacked leaf the program serves: one model on both sides."""
    tiny = load_data(os.path.join(ROOT, configs()[0]), rehearse=True)
    family = load_family(tiny)
    tree = server.program_tree(tiny, SEEDS[0])
    sz, root = family.sizes(tiny), W.root_key(SEEDS[0])
    for key, kind, first, count in family.stacks(sz):
        for i in range(count):
            for name, leaf in family.layer_leaves(
                    root, kind, first + i, sz).items():
                served = tree[key][name]
                if isinstance(leaf, tuple):
                    np.testing.assert_array_equal(served.q[i], leaf[0])
                    np.testing.assert_array_equal(served.scale[i, 0], leaf[1])
                    np.testing.assert_array_equal(
                        served.dequantize()[i], W.as_float32(leaf))
                else:
                    np.testing.assert_array_equal(served[i], leaf)


@pytest.mark.parametrize("file", configs())
def test_a_family_answers_every_question(file):
    with open(os.path.join(ROOT, file)) as f:
        config = json.load(f)
    family = load_family(config)
    assert all(hasattr(family, name) for name in FAMILY_ANSWERS)
    assert {"embed", "final_norm", "lm_head"} <= set(family.LEAF_NO)
    numbers = list(family.LEAF_NO.values())
    assert len(numbers) == len(set(numbers)), "two leaves share a key"
    sz = family.sizes(config)
    assert {"d", "v", "L"} <= set(sz)
    layers = [first + i for _k, _kind, first, n in family.stacks(sz)
              for i in range(n)]
    assert layers == list(range(sz["L"])), "every layer once, in order"
    mc = family.model_config(config)
    fields = set(server._flat(dataclasses.asdict(mc)))
    assert set(family.REDUCED.values()) <= fields
    assert set(config["reduced"]) <= set(family.REDUCED)
    assert bytes_model.step_floor_bytes(config, 1000.0, 16.0) > (
        bytes_model.weight_bytes(config)
        + 1000 * bytes_model.kv_token_bytes(config)) > 0
    assert isinstance(family.SCOPES, tuple)


def test_a_module_that_leaves_an_answer_out_is_refused(tmp_path, monkeypatch):
    from benchmarks import loading

    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "half.py").write_text("SCOPES = ()\n")
    monkeypatch.setattr(loading, "HERE", str(tmp_path))
    with pytest.raises(AttributeError, match="does not answer"):
        load_family({"family": "half"})
    with pytest.raises(FileNotFoundError):
        load_family({"family": "none-such"})
    with pytest.raises(KeyError, match="family"):
        load_family({"reference": "qwen2"})


# -- the preset rule ---------------------------------------------------------------
def full(file: str) -> dict:
    with open(os.path.join(ROOT, file)) as f:
        return json.load(f)


@pytest.mark.parametrize("file", configs())
def test_a_configuration_as_committed_equals_its_preset(file):
    config = full(file)
    server.check_against_preset(config, server.model_config(config))


@pytest.mark.parametrize("change,said", [
    ({"num_attention_heads": 14}, "num_heads"),          # a width: never
    ({"rope_theta": 10000.0}, "rope_theta"),
    ({"num_hidden_layers": 4, "reduced": []}, "num_layers"),   # not listed
    ({"reduced": ["num_hidden_layers", "vocab_size"]}, "cannot cut"),
])
def test_only_what_the_familys_map_allows_may_differ(change, said):
    config = dict(full(configs()[-1]), **change)
    with pytest.raises(SystemExit, match=said):
        server.check_against_preset(config, server.model_config(config))


def test_nested_fields_are_compared_by_dotted_name():
    assert server._flat({"a": 1, "moe": {"num_experts": 8, "x": {"y": 2}},
                         "mla": None}) == {
        "a": 1, "moe.num_experts": 8, "moe.x.y": 2, "mla": None}


# -- what weights.py offers a family with experts ------------------------------------
def test_stacked_matrices_are_one_leaf_told_apart_by_their_part():
    root = W.root_key(SEEDS[2])
    q, scale = W.matrices(root, 9, 2, 3, 16, 8)
    assert q.shape == (3, 16, 8) and scale.shape == (3, 8)
    for part in range(3):
        one_q, one_scale = W.matrix(root, 9, 2, 16, 8, part)
        np.testing.assert_array_equal(q[part], one_q)
        np.testing.assert_array_equal(scale[part], one_scale)
    assert not np.array_equal(q[0], q[1])
    # a part is no layer and no other leaf
    assert not np.array_equal(q[1], W.matrix(root, 9, 1, 16, 8)[0])
    assert not np.array_equal(q[0], W.matrix(root, 9, 2, 16, 8)[0])
    for bits in (8, 4):
        whole = W.as_float32((q, scale), bits)
        assert whole.shape == (3, 16, 8) and whole.dtype == np.float32
        np.testing.assert_array_equal(
            whole[2], W.dequantize(q[2], scale[2], weight_bits=bits))
    router = W.float_matrix(root, 4, 0, 64, 4)
    assert router.dtype == np.float32 and router.shape == (64, 4)
    assert 0.05 < float(np.std(router)) < 0.25        # near 64 ** -0.5
    np.testing.assert_array_equal(W.as_float32(router), router)


# -- the throw-away family the rehearsal adds: its bytes, by hand ---------------------
def test_the_fixtures_pages_hold_the_latent_and_its_step_reaches_every_expert(
        monkeypatch):
    from benchmarks import loading

    here = os.path.join(ROOT, "tests", "benchmarks", "fixture_family")
    with open(os.path.join(here, "configs", "tiny-latent-experts.json")) as f:
        config = json.load(f)
    monkeypatch.setattr(loading, "HERE", here)
    family = load_family(config)
    sz = family.sizes(config)
    assert [s[:2] for s in family.stacks(sz)] == [
        ("layers", "dense"), ("moe_layers", "experts")]
    assert [s[2:] for s in family.stacks(sz)] == [(0, 1), (1, 2)]
    # a token's page entry is the latent (32) and the shared rotary key (8),
    # float32 here, in each of 3 layers: not 2 x 4 heads x 24
    assert family.kv_token_bytes(config) == 3 * (32 + 8) * 4
    attention = (64 * 32 + 32 * 96 + 64 * 32 + 64 * 8 + 32 * 128 + 96 * 64
                 + 4 * (32 + 96 + 32 + 8 + 128 + 64) + 4 * (64 + 32 + 32 + 64))
    dense = 3 * 64 * 128 + 4 * (128 + 128 + 64)
    one_expert = 3 * 64 * 64 + 4 * (64 + 64 + 64)
    experts = 5 * one_expert + 4 * (64 * 4 + 4)        # shared + 4 routed, router
    head = 64 * 512 + 4 * 512 + 4 * 64
    assert family.weight_bytes(config) == (
        3 * attention + dense + 2 * experts + head)
    assert family.step_floor_bytes(config, 100.0, 16.0) == (
        family.weight_bytes(config) + 100 * 480 + 16 * 64 * 4)
    leaves = family.layer_leaves(W.root_key(1), "experts", 2, sz)
    assert leaves["eg"][0].shape == (4, 64, 64) and leaves["ed"][1].shape == (4, 64)
    assert leaves["router"].dtype == np.float32 and leaves["router"].shape == (64, 4)
    assert leaves["router_bias"].dtype == np.float32
    assert "wg" not in leaves and "sg" in leaves
    assert "eg" not in family.layer_leaves(W.root_key(1), "dense", 0, sz)
    mc = family.model_config(config)
    assert mc.mla.latent_cache and mc.moe_layer_start == 1
    assert set(family.REDUCED.values()) <= set(
        server._flat(dataclasses.asdict(mc)))
