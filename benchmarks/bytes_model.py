"""The least bytes a step of the served model has to move, from shapes.

Counts only what the algorithm needs: every weight a step's tokens can
reach, in the stack and the output head, read once in the type it is stored
in, the embedding rows of the step's tokens, and what the pages hold of the
resident tokens read once. Temporaries, re-reads, padding and layout copies
are what the program adds, and are what the share of this floor exposes.

The counting belongs to the model's family (which weights a token can
reach where there are experts, what a page holds where the cache is a
latent): the three functions here ask ``families/<family>.py``.
"""

from __future__ import annotations

import json
import os

from benchmarks.loading import load_family

BYTES = {"int8": 1, "bfloat16": 2, "float32": 4}


def weight_bytes(config: dict) -> int:
    """Bytes of one pass over the stack and the head."""
    return load_family(config).weight_bytes(config)


def kv_token_bytes(config: dict) -> int:
    """Bytes the pages hold of one resident token over all layers."""
    return load_family(config).kv_token_bytes(config)


def step_floor_bytes(config: dict, resident_tokens: float,
                     step_tokens: float = 0.0) -> float:
    """Weights once, the resident tokens' pages once, and the embedding
    rows of the tokens the step carries."""
    return load_family(config).step_floor_bytes(
        config, resident_tokens, step_tokens)


def peaks(device_kind: str) -> dict:
    """Published peaks of a device; an unknown device is an error."""
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table[device_kind]
