"""The least bytes a step of the served model has to move, from shapes.

Counts only what the algorithm needs: every weight of the decoder stack and
of the output head read once in the type it is stored in, the embedding
rows of the step's tokens, and the keys and values of the resident tokens
read once. Temporaries, re-reads, padding and layout copies are what the
program adds, and are what the share of this floor exposes.
"""

from __future__ import annotations

import json
import os

_BYTES = {"int8": 1, "bfloat16": 2, "float32": 4}


def _sizes(config: dict) -> dict:
    heads = config["num_attention_heads"]
    head_dim = config.get("head_dim") or config["hidden_size"] // heads
    return {
        "d": config["hidden_size"], "f": config["intermediate_size"],
        "v": config["vocab_size"], "L": config["num_hidden_layers"],
        "q": heads * head_dim,
        "kv": config["num_key_value_heads"] * head_dim,
    }


def weight_bytes(config: dict) -> int:
    """Bytes of one pass over the stack and the head: int8 matrices with a
    float32 scale for each output channel, bfloat16 norms and biases."""
    s = _sizes(config)
    w = _BYTES[config["precision"]["weights"]]
    vec = _BYTES[config["precision"]["compute"]]
    matrices = [
        (s["d"], s["q"]), (s["d"], s["kv"]), (s["d"], s["kv"]),
        (s["q"], s["d"]), (s["d"], s["f"]), (s["d"], s["f"]),
        (s["f"], s["d"]),
    ]
    layer = sum(a * b * w + b * 4 for a, b in matrices)
    layer += (s["q"] + 2 * s["kv"] + 2 * s["d"]) * vec
    head = s["d"] * s["v"] * w + s["v"] * 4 + s["d"] * vec
    return s["L"] * layer + head


def kv_token_bytes(config: dict) -> int:
    """Bytes of one resident token's keys and values over all layers."""
    s = _sizes(config)
    return 2 * s["L"] * s["kv"] * _BYTES[config["precision"]["kv_pages"]]


def step_floor_bytes(config: dict, resident_tokens: float,
                     step_tokens: float = 0.0) -> float:
    """Weights once, the resident keys and values once, and the embedding
    rows of the tokens the step carries."""
    s = _sizes(config)
    embed = step_tokens * s["d"] * _BYTES[config["precision"]["compute"]]
    return (weight_bytes(config)
            + resident_tokens * kv_token_bytes(config) + embed)


def peaks(device_kind: str) -> dict:
    """Published peaks of a device; an unknown device is an error."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]
