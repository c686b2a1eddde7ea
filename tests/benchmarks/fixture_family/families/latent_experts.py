"""A throw-away decoder family, for the test that adds a family to the
harness from new files only: latent attention with a low-rank query and a
decoupled rotary part, the pages holding the latent; leading dense layers,
then layers of routed experts behind a float32 sigmoid router with one
shared expert. It stands for no model.

What it answers is what ``benchmarks/loading.py`` ``FAMILY_ANSWERS`` lists;
the mathematics is in ``reference/latent_experts.py``.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmarks import weights as W
from benchmarks.bytes_model import BYTES

REDUCED = {
    "num_hidden_layers": "num_layers",
    "n_routed_experts": "moe.num_experts",
    "vocab_size": "vocab_size",
}
SCOPES = ()

# (leaf, fan-in size key, fan-out size key) of the 2-D int8 matrices
ATTENTION = {
    "wdq": ("d", "rq"), "wuq": ("rq", "Hq"), "wdkv": ("d", "rkv"),
    "wkr": ("d", "dr"), "wukv": ("rkv", "Hkv"), "wo": ("Hq", "d"),
}
DENSE = {"wg": ("d", "f"), "wu": ("d", "f"), "wd": ("f", "d")}
SHARED = {"sg": ("d", "fs"), "su": ("d", "fs"), "sd": ("fs", "d")}
EXPERTS = {"eg": ("d", "fe"), "eu": ("d", "fe"), "ed": ("fe", "d")}
NORMS = {"attn_norm": "d", "q_norm": "rq", "kv_norm": "rkv", "mlp_norm": "d"}
LEAF_NO = {name: i for i, name in enumerate([
    *ATTENTION, *DENSE, *SHARED, *EXPERTS, *NORMS, "router", "router_bias",
    "embed", "final_norm", "lm_head",
])}


# -- 1. the program's model ------------------------------------------------------
def model_config(config: dict):
    from opsagent_tpu.models.config import MLAConfig, ModelConfig, MoEConfig

    heads = config["num_attention_heads"]
    return ModelConfig(
        name=config["preset"],
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=heads,
        num_kv_heads=heads,
        head_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        rope_theta=config["rope_theta"],
        rms_norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        max_position=config["max_position_embeddings"],
        moe=MoEConfig(
            num_experts=config["n_routed_experts"],
            num_experts_per_token=config["num_experts_per_tok"],
            num_shared_experts=config["n_shared_experts"],
            expert_intermediate_size=config["moe_intermediate_size"],
            norm_topk_prob=config["norm_topk_prob"],
            routed_scaling_factor=config["routed_scaling_factor"],
            scoring_func=config["scoring_func"],
        ),
        moe_layer_start=config["first_k_dense_replace"],
        mla=MLAConfig(
            q_lora_rank=config["q_lora_rank"],
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            latent_cache=True,
        ),
    )


# -- 2. the seeded leaves --------------------------------------------------------
def sizes(config: dict) -> dict:
    heads = config["num_attention_heads"]
    dn, dr, dv = (config[k] for k in (
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))
    fe = config["moe_intermediate_size"]
    return {
        "d": config["hidden_size"], "f": config["intermediate_size"],
        "fe": fe, "fs": fe * config["n_shared_experts"],
        "v": config["vocab_size"], "L": config["num_hidden_layers"],
        "Ld": config["first_k_dense_replace"],
        "H": heads, "dn": dn, "dr": dr, "dv": dv,
        "rq": config["q_lora_rank"], "rkv": config["kv_lora_rank"],
        # a head's query is [content ; rotary]; the program pads a head's
        # value to that width, so ``wo`` has H (dn + dr) rows
        "Hq": heads * (dn + dr), "Hkv": heads * (dn + dv),
        "E": config["n_routed_experts"], "k": config["num_experts_per_tok"],
    }


def stacks(sz: dict) -> tuple:
    return (("layers", "dense", 0, sz["Ld"]),
            ("moe_layers", "experts", sz["Ld"], sz["L"] - sz["Ld"]))


def layer_leaves(root, kind: str, layer, sz: dict) -> dict:
    def matrix(name, a, b):
        return W.matrix(root, LEAF_NO[name], layer, sz[a], sz[b])

    out = {name: matrix(name, a, b) for name, (a, b) in ATTENTION.items()}
    out.update({name: W.norm(root, LEAF_NO[name], layer, sz[n])
                for name, n in NORMS.items()})
    if kind == "dense":
        out.update({name: matrix(name, a, b) for name, (a, b) in DENSE.items()})
        return out
    out.update({name: matrix(name, a, b) for name, (a, b) in SHARED.items()})
    out.update({
        name: W.matrices(root, LEAF_NO[name], layer, sz["E"], sz[a], sz[b])
        for name, (a, b) in EXPERTS.items()})
    out["router"] = W.float_matrix(
        root, LEAF_NO["router"], layer, sz["d"], sz["E"])
    out["router_bias"] = W.vector(
        root, LEAF_NO["router_bias"], layer, sz["E"], 0.0, 0.05
    ).astype(jnp.float32)
    return out


# -- 3. the reference's call -----------------------------------------------------
def position_tables(ref, length: int, config: dict, sz: dict):
    return ref.rope_tables(length, sz["dr"], config["rope_theta"])


def apply_layer(ref, kind: str, seq, w: dict, tables, config: dict, sz: dict):
    """The served ``wo`` has a head's value padded to the query's width
    (rows that multiply zeros): the reference gets the rows that count."""
    cos, sin = tables
    H, dq, dv = sz["H"], sz["dn"] + sz["dr"], sz["dv"]
    wo = w["wo"].reshape(H, dq, -1)[:, :dv].reshape(H * dv, -1)
    return ref.layer(
        seq, dict(w, wo=wo), cos, sin, kind=kind, heads=H, nope=sz["dn"],
        rope=sz["dr"], top_k=sz["k"],
        scale=config["routed_scaling_factor"], eps=config["rms_norm_eps"])


# -- 4. the bytes of a step ------------------------------------------------------
def _matrix_bytes(sz: dict, table: dict, w: int, count: int = 1) -> int:
    """int8 matrices with a float32 scale for each output channel."""
    return count * sum(sz[a] * sz[b] * w + sz[b] * 4 for a, b in table.values())


def weight_bytes(config: dict) -> int:
    """Every layer's attention, the dense layers' feed-forward, and of an
    expert layer the router, the shared expert and EVERY routed expert held
    here: a step of E / k tokens or more can reach them all, and the
    program's all-experts scan reads them all at any size."""
    s = sizes(config)
    w = BYTES[config["precision"]["weights"]]
    vec = BYTES[config["precision"]["compute"]]
    attention = _matrix_bytes(s, ATTENTION, w) + vec * sum(
        s[n] for n in NORMS.values())
    dense = _matrix_bytes(s, DENSE, w)
    experts = (_matrix_bytes(s, SHARED, w)
               + _matrix_bytes(s, EXPERTS, w, s["E"])
               + 4 * (s["d"] * s["E"] + s["E"]))
    head = s["d"] * s["v"] * w + s["v"] * 4 + s["d"] * vec
    return (s["L"] * attention + s["Ld"] * dense
            + (s["L"] - s["Ld"]) * experts + head)


def kv_token_bytes(config: dict) -> int:
    """What the pages hold of a token: the latent and the rotary key."""
    s = sizes(config)
    return s["L"] * (s["rkv"] + s["dr"]) * BYTES[config["precision"]["kv_pages"]]


def step_floor_bytes(config: dict, resident_tokens: float,
                     step_tokens: float = 0.0) -> float:
    embed = (step_tokens * config["hidden_size"]
             * BYTES[config["precision"]["compute"]])
    return (weight_bytes(config)
            + resident_tokens * kv_token_bytes(config) + embed)
