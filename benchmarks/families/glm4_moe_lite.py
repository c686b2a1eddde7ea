"""The GLM-4.7-Flash decoder family (zai-org/GLM-4.7-Flash, ``model_type:
glm4_moe_lite``) as the harness needs it: multi-head latent attention with a
low-rank query and a decoupled rotary part in every layer, the pages holding
the latent ``[c_kv | k_r]``; leading dense layers, then layers of routed
experts behind a float32 sigmoid router with a selection bias, beside one
shared expert. Every routed expert is held here: the cut is in depth alone.

A configuration's file names its family (``"family": "glm4_moe_lite"``) and
``loading.load_family`` finds this module by that name. The mathematics is in
``reference/glm4_moe_lite.py``, which this module only calls. Beside what
``loading.FAMILY_ANSWERS`` lists it keeps the functions that give the least
bytes of the two parts this family's metrics read (``mla_attn_floor_bytes``,
``moe_experts_floor_bytes``), which the ``kernels.*_hbm_share`` readers use.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from benchmarks import weights as W
from benchmarks.bytes_model import BYTES

REDUCED = {"num_hidden_layers": "num_layers"}

# Scope names this family's program adds to ``scope_reduce.SCOPES``: the four
# with a ``kernels.*_ms`` reader. ``moe_shared`` stays a plain scope, charged
# to the ``ffn`` that encloses it.
SCOPES = ("mla_absorb", "mla_latent", "moe_experts", "moe_router")

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
# The matrices that write to the residual stream. They are drawn at the
# depth-scaled initialisation (GPT-2 / Megatron: fan_in ** -0.5 over
# sqrt(2 x the PUBLISHED depth); ``out_scale``), the scale such a model is
# trained from: a
# layer then moves the stream by a few percent, as a trained model's does. At
# the plain fan-in scale a layer moves it by a third, and a near-tied expert
# that flips under bfloat16 (top-4 of 64 at weights of 1.8 / 4 each) moves
# the logits by their whole spread: the sound program read ``gap_max`` 2.7-3.3
# and agreed with the float32 reference on 71-74 % of served tokens, the
# error growing by 3 % a layer of experts and 0.5 % in the dense one (my chip
# runs, PR 40; PERF.md section 6), so no limit could tell it from a fault.
RESIDUAL_OUT = ("wo", "wd", "sd", "ed")


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
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        attn_bias=config["attention_bias"],
        tie_embeddings=config["tie_word_embeddings"],
        max_position=config["max_position_embeddings"],
        moe=MoEConfig(
            num_experts=config["n_routed_experts"],
            num_experts_per_token=config["num_experts_per_tok"],
            num_shared_experts=config["n_shared_experts"],
            expert_intermediate_size=config["moe_intermediate_size"],
            norm_topk_prob=config["norm_topk_prob"],
            routed_scaling_factor=float(config["routed_scaling_factor"]),
            scoring_func="sigmoid",         # what ``noaux_tc`` scores with
            n_group=config["n_group"],
            topk_group=config["topk_group"],
            router_experts=config["n_routed_experts"],
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
        # a head's query is [content ; rotary]; the program holds ``wo`` with
        # a head's value at that width (this model's is: 256 = 192 + 64)
        "Hq": heads * (dn + dr), "Hkv": heads * (dn + dv),
        "E": config["n_routed_experts"], "k": config["num_experts_per_tok"],
        "out_scale": out_scale(config.get(
            "source_num_hidden_layers", config["num_hidden_layers"])),
    }


def out_scale(depth: int) -> float:
    """``(2 x depth) ** -0.5`` to the nearest power of two (1/8 at 47
    layers, for 0.103): times a power of two a float32 scale is the same
    number in whatever order a compiler multiplies, so the program's tree
    and the reference's leaves stay equal to the bit."""
    return 2.0 ** round(math.log2((2.0 * depth) ** -0.5))


def stacks(sz: dict) -> tuple:
    return (("layers", "dense", 0, sz["Ld"]),
            ("moe_layers", "experts", sz["Ld"], sz["L"] - sz["Ld"]))


def layer_leaves(root, kind: str, layer, sz: dict) -> dict:
    """One layer as served: ``name -> (q, scale)`` or an array."""
    def scaled(name, pair):
        q, scale = pair
        return q, scale * sz["out_scale"] if name in RESIDUAL_OUT else scale

    def matrix(name, a, b):
        return scaled(name, W.matrix(
            root, LEAF_NO[name], layer, sz[a], sz[b]))

    out = {name: matrix(name, a, b) for name, (a, b) in ATTENTION.items()}
    out.update({name: W.norm(root, LEAF_NO[name], layer, sz[n])
                for name, n in NORMS.items()})
    if kind == "dense":
        out.update({name: matrix(name, a, b) for name, (a, b) in DENSE.items()})
        return out
    out.update({name: matrix(name, a, b) for name, (a, b) in SHARED.items()})
    out.update({
        name: scaled(name, W.matrices(
            root, LEAF_NO[name], layer, sz["E"], sz[a], sz[b]))
        for name, (a, b) in EXPERTS.items()})
    out["router"] = W.float_matrix(
        root, LEAF_NO["router"], layer, sz["d"], sz["E"])
    # not zero (``assumed``): a bias that reached the weights would show
    out["router_bias"] = W.vector(
        root, LEAF_NO["router_bias"], layer, sz["E"], 0.0, 0.05
    ).astype(jnp.float32)
    return out


# -- 3. the reference's call -----------------------------------------------------
def position_tables(ref, length: int, config: dict, sz: dict):
    return ref.rope_tables(length, sz["dr"], float(config["rope_theta"]))


def apply_layer(ref, kind: str, seq, w: dict, tables, config: dict, sz: dict):
    """One layer of ``kind`` on one whole sequence [T, d] float32. The
    served ``wo`` holds a head's value at the query's width; the reference
    gets the rows that count (all of them where the two are equal)."""
    cos, sin = tables
    H, dq, dv = sz["H"], sz["dn"] + sz["dr"], sz["dv"]
    wo = w["wo"].reshape(H, dq, -1)[:, :dv].reshape(H * dv, -1)
    return ref.layer(
        seq, dict(w, wo=wo), cos, sin, kind=kind, heads=H, nope=sz["dn"],
        rope=sz["dr"], top_k=sz["k"],
        scale=float(config["routed_scaling_factor"]),
        eps=config["rms_norm_eps"])


# -- 4. the bytes of a step ------------------------------------------------------
def _matrix_bytes(sz: dict, table: dict, w: int, count: float = 1) -> float:
    """int8 matrices with a float32 scale for each output channel."""
    return count * sum(sz[a] * sz[b] * w + sz[b] * 4 for a, b in table.values())


def moe_layers(config: dict) -> int:
    """Layers with routed experts: all but the leading dense ones."""
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def moe_experts_floor_bytes(config: dict, experts: float) -> float:
    """Least bytes the routed experts' matmuls of ONE layer move when
    ``experts`` distinct experts have work: each one's three int8 matrices
    and scales once (its tokens' activations are not counted)."""
    return _matrix_bytes(
        sizes(config), EXPERTS, BYTES[config["precision"]["weights"]], experts)


def latent_token_bytes(config: dict) -> int:
    """What ONE layer's pages hold of a token: ``[c_kv | k_r]``."""
    s = sizes(config)
    return (s["rkv"] + s["dr"]) * BYTES[config["precision"]["kv_pages"]]


def mla_attn_floor_bytes(config: dict, live_tokens: float) -> float:
    """Least bytes the attention of ONE pass reads of the pages: the latent
    of the ``live_tokens`` context tokens of its rows, once in every layer
    (queries, scores and the output are not counted)."""
    return (live_tokens * latent_token_bytes(config)
            * config["num_hidden_layers"])


def _weight_bytes(config: dict, experts: float) -> float:
    """One pass over the stack and the head with ``experts`` routed experts
    of each expert layer read: int8 matrices with float32 scales, float32
    router and selection bias, norms in the compute type."""
    s = sizes(config)
    w = BYTES[config["precision"]["weights"]]
    vec = BYTES[config["precision"]["compute"]]
    attention = _matrix_bytes(s, ATTENTION, w) + vec * sum(
        s[n] for n in NORMS.values())
    moe = (_matrix_bytes(s, SHARED, w) + _matrix_bytes(s, EXPERTS, w, experts)
           + 4 * (s["d"] * s["E"] + s["E"]))
    head = s["d"] * s["v"] * w + s["v"] * 4 + s["d"] * vec
    return (s["L"] * attention + s["Ld"] * _matrix_bytes(s, DENSE, w)
            + moe_layers(config) * moe + head)


def weight_bytes(config: dict) -> float:
    """Bytes of the weights the LEAST pass reads: of each expert layer the
    experts one token reaches (``num_experts_per_tok``), every other weight
    once. A pass of the engine's 16 rows x top-4 can reach all 64 experts
    of a layer (``held_weight_bytes``); a floor counts what no pass can do
    without."""
    return _weight_bytes(config, config["num_experts_per_tok"])


def held_weight_bytes(config: dict) -> float:
    """Bytes of every weight held here but the embedding: what a pass
    reads once its tokens reach every expert."""
    return _weight_bytes(config, config["n_routed_experts"])


def kv_token_bytes(config: dict) -> int:
    """Bytes the pages hold of one resident token over all layers."""
    return config["num_hidden_layers"] * latent_token_bytes(config)


def step_floor_bytes(config: dict, resident_tokens: float,
                     step_tokens: float = 0.0) -> float:
    """A floor no pass can beat: the routed experts ONE token reaches in
    each expert layer (``num_experts_per_tok``; a pass of more tokens reads
    more, up to all), every other weight once, the resident latent once and
    the embedding rows of the tokens the step carries."""
    embed = (step_tokens * config["hidden_size"]
             * BYTES[config["precision"]["compute"]])
    return (weight_bytes(config)
            + resident_tokens * kv_token_bytes(config) + embed)
