"""Find a generator, a per-layer reader, a reference or a model family by
its name."""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(kind: str, name: str):
    """The module ``benchmarks/<kind>/<name>.py`` (names may hold dots)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind}/{name}.py")
    safe = name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{safe}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# What a family's module answers; ``families/qwen2.py`` is the first.
FAMILY_ANSWERS = (
    # 1. the program's model
    "model_config",     # (config) -> the program's ModelConfig
    "REDUCED",          # key under ``reduced`` -> ModelConfig field that may
                        # then differ from the preset (dotted where nested)
    # 2. the seeded leaves
    "LEAF_NO",          # leaf -> the number its key is folded from; holds
                        # ``embed``, ``final_norm`` and ``lm_head`` too
    "sizes",            # (config) -> sizes by short name; ``d``, ``v``, ``L``
    "stacks",           # (sizes) -> ((key of the served tree, kind of layer,
                        # first layer, layers), ...), in the model's order
    "layer_leaves",     # (root, kind, layer, sizes) -> {leaf: (int8, scale)
                        # | array}: one layer as served, unstacked
    # 3. the reference's call
    "position_tables",  # (ref, length, config, sizes) -> what apply_layer takes
    "apply_layer",      # (ref, kind, seq, float32 leaves, tables, config,
                        # sizes) -> seq: one layer on one whole sequence
    # 4. the bytes of a step
    "weight_bytes",     # (config) -> bytes a pass reads of the weights
    "kv_token_bytes",   # (config) -> bytes the pages hold of one token
    "step_floor_bytes",  # (config, resident tokens, step tokens) -> bytes
    # 5. the scope names
    "SCOPES",           # names the family's program adds to scope_reduce's
)


def load_family(config: dict):
    """The module ``families/<config["family"]>.py``: everything the
    harness knows of a model family, found by the name the configuration's
    file gives. A module that leaves an answer out is refused here."""
    if "family" not in config:
        raise KeyError("the configuration's file names no \"family\"")
    mod = load_module("families", config["family"])
    missing = [name for name in FAMILY_ANSWERS if not hasattr(mod, name)]
    if missing:
        raise AttributeError(
            f"families/{config['family']}.py does not answer {missing}")
    return mod


def load_data(path: str, rehearse: bool = False) -> dict:
    """A configuration or traffic file; with ``rehearse`` its ``rehearsal``
    group (the tiny sizes of a CPU walk-through) laid over it."""
    with open(path) as f:
        data = json.load(f)
    if rehearse:
        data.update(data.get("rehearsal", {}))
    return data
