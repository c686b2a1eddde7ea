"""The Qwen2 decoder family (Qwen2 / Qwen2.5) as the harness needs it:
dense layers of grouped-query attention with Q/K/V biases and a SwiGLU
feed-forward, one stack, keys and values in the pages.

A configuration's file names its family (``"family": "qwen2"``) and
``loading.load_family`` finds this module by that name. What a family
answers is listed there; the mathematics is not here but in
``reference/qwen2.py``, which this module only calls.
"""

from __future__ import annotations

from benchmarks import weights as W
from benchmarks.bytes_model import BYTES

# A key under ``reduced`` -> the field of the program's ModelConfig that may
# then differ from the preset (dotted where the field is nested). Every
# other field equals the preset.
REDUCED = {"num_hidden_layers": "num_layers"}

# Scope names this family's program adds to ``scope_reduce.SCOPES``: none.
SCOPES = ()

# (leaf, fan-in size key, fan-out size key); sizes come from ``sizes()``.
MATRICES = {
    "wq": ("d", "q"), "wk": ("d", "kv"), "wv": ("d", "kv"), "wo": ("q", "d"),
    "wg": ("d", "f"), "wu": ("d", "f"), "wd": ("f", "d"),
}
BIASES = {"bq": "q", "bk": "kv", "bv": "kv"}
NORMS = ("attn_norm", "mlp_norm")
# The number a leaf's key is folded from: part of what a seed means, so
# the order never changes and a new leaf goes at the end.
LEAF_NO = {name: i for i, name in enumerate(
    [*MATRICES, *BIASES, *NORMS, "embed", "final_norm", "lm_head"]
)}


# -- 1. the program's model ------------------------------------------------------
def model_config(config: dict):
    """The program's ModelConfig from the file's published keys."""
    from opsagent_tpu.models.config import ModelConfig

    return ModelConfig(
        name=config["preset"],
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        rope_theta=config["rope_theta"],
        rms_norm_eps=config["rms_norm_eps"],
        attn_bias=True,
        tie_embeddings=config["tie_word_embeddings"],
        max_position=config["max_position_embeddings"],
    )


# -- 2. the seeded leaves --------------------------------------------------------
def sizes(config: dict) -> dict:
    """Matrix sizes from a configuration file's published keys."""
    heads = config["num_attention_heads"]
    head_dim = config.get("head_dim") or config["hidden_size"] // heads
    return {
        "d": config["hidden_size"], "f": config["intermediate_size"],
        "v": config["vocab_size"], "L": config["num_hidden_layers"],
        "H": heads, "K": config["num_key_value_heads"], "D": head_dim,
        "q": heads * head_dim,
        "kv": config["num_key_value_heads"] * head_dim,
    }


def stacks(sz: dict) -> tuple:
    """(key of the served tree, kind of layer, first layer, layers)."""
    return (("layers", "dense", 0, sz["L"]),)


def layer_leaves(root, kind: str, layer, sz: dict) -> dict:
    """One layer as served: ``name -> (q, scale)`` or a bfloat16 vector."""
    out = {
        name: W.matrix(root, LEAF_NO[name], layer, sz[a], sz[b])
        for name, (a, b) in MATRICES.items()
    }
    out.update({name: W.vector(root, LEAF_NO[name], layer, sz[n], 0.0, 0.1)
                for name, n in BIASES.items()})
    out.update({name: W.norm(root, LEAF_NO[name], layer, sz["d"])
                for name in NORMS})
    return out


# -- 3. the reference's call -----------------------------------------------------
def position_tables(ref, length: int, config: dict, sz: dict):
    return ref.rope_tables(length, sz["D"], config["rope_theta"])


def apply_layer(ref, kind: str, seq, w: dict, tables, config: dict, sz: dict):
    """One layer of ``kind`` on one whole sequence [T, d] float32."""
    cos, sin = tables
    return ref.layer(seq, w, cos, sin, heads=sz["H"], kv_heads=sz["K"],
                     eps=config["rms_norm_eps"])


# -- 4. the bytes of a step ------------------------------------------------------
def weight_bytes(config: dict) -> int:
    """Bytes of one pass over the stack and the head: int8 matrices with a
    float32 scale for each output channel, bfloat16 norms and biases."""
    s = sizes(config)
    w = BYTES[config["precision"]["weights"]]
    vec = BYTES[config["precision"]["compute"]]
    layer = sum(s[a] * s[b] * w + s[b] * 4 for a, b in MATRICES.values())
    layer += (s["q"] + 2 * s["kv"] + 2 * s["d"]) * vec
    head = s["d"] * s["v"] * w + s["v"] * 4 + s["d"] * vec
    return s["L"] * layer + head


def kv_token_bytes(config: dict) -> int:
    """Bytes of one resident token's keys and values over all layers."""
    s = sizes(config)
    return 2 * s["L"] * s["kv"] * BYTES[config["precision"]["kv_pages"]]


def step_floor_bytes(config: dict, resident_tokens: float,
                     step_tokens: float = 0.0) -> float:
    """Weights once, the resident keys and values once, and the embedding
    rows of the tokens the step carries."""
    embed = (step_tokens * config["hidden_size"]
             * BYTES[config["precision"]["compute"]])
    return (weight_bytes(config)
            + resident_tokens * kv_token_bytes(config) + embed)
