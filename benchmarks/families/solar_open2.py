"""The Solar-Open2 decoder family (upstage/Solar-Open2-250B) as the harness
needs it: periods of one gated NoPE grouped-query attention layer and three
delta-rule linear-attention layers, every layer with routed experts behind a
float32 sigmoid router and one shared expert; the configuration holds one
chip's share of an expert-parallel deployment (``n_routed_experts`` experts
HELD of the router's ``source_n_routed_experts``, an eighth of the
vocabulary).

A configuration's file names its family (``"family": "solar_open2"``) and
``loading.load_family`` finds this module by that name. The mathematics is in
``reference/solar_open2.py``, which this module only calls. Beside what
``loading.FAMILY_ANSWERS`` lists it keeps the functions that give the least
bytes of the two kernels this family adds (``lin_scan_floor_bytes``,
``moe_experts_floor_bytes``), which the ``kernels.*_hbm_share`` readers use.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import weights as W
from benchmarks.bytes_model import BYTES

REDUCED = {
    "num_hidden_layers": "num_layers",
    "n_routed_experts": "moe.num_experts",
    "vocab_size": "vocab_size",
}

# Scope names this family's program adds to ``scope_reduce.SCOPES``: the five
# with a ``kernels.*_ms`` reader. ``attn_gate`` and ``moe_shared`` stay plain
# scopes, charged to the ``attn_out`` and ``ffn`` that enclose them.
SCOPES = ("lin_scan", "lin_proj", "state_io", "moe_experts", "moe_router")

# (leaf, fan-in size key, fan-out size key) of the 2-D int8 matrices
GQA = {"wq": ("d", "q"), "wk": ("d", "kv"), "wv": ("d", "kv"),
       "wo": ("q", "d"), "wgate": ("d", "q")}
LINEAR = {"lq": ("d", "lk"), "lk": ("d", "lk"), "lv": ("d", "lv"),
          "lo": ("lv", "d"), "f_down": ("d", "r"), "f_up": ("r", "lk"),
          "g_down": ("d", "r"), "g_up": ("r", "lv"), "wb": ("d", "LH")}
SHARED = {"sg": ("d", "fs"), "su": ("d", "fs"), "sd": ("fs", "d")}
EXPERTS = {"eg": ("d", "fe"), "eu": ("d", "fe"), "ed": ("fe", "d")}
NORMS = {"attn_norm": "d", "mlp_norm": "d"}
LEAF_NO = {name: i for i, name in enumerate([
    *GQA, *LINEAR, *SHARED, *EXPERTS, *NORMS, "o_norm", "conv", "a_log",
    "dt_bias", "router", "router_bias", "embed", "final_norm", "lm_head",
])}


# -- 1. the program's model ------------------------------------------------------
def model_config(config: dict):
    from opsagent_tpu.models.config import (
        LinearAttnConfig, ModelConfig, MoEConfig,
    )

    la = config["linear_attn_config"]
    period = config["gqa_interval"] + 1
    return ModelConfig(
        name=config["preset"],
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        max_position=config["max_position_embeddings"],
        moe=MoEConfig(
            num_experts=config["n_routed_experts"],
            num_experts_per_token=config["num_experts_per_tok"],
            num_shared_experts=config["n_shared_experts"],
            expert_intermediate_size=config["moe_intermediate_size"],
            norm_topk_prob=config["norm_topk_prob"],
            routed_scaling_factor=float(config["routed_scaling_factor"]),
            scoring_func="sigmoid",
            router_experts=config.get(
                "source_n_routed_experts", config["n_routed_experts"]),
            first_expert=config.get("first_expert_held", 0),
        ),
        moe_layer_start=config["first_k_dense_replace"],
        mixer_period=("attn",) + ("linear",) * (period - 1),
        linear_attn=LinearAttnConfig(
            num_heads=la["num_heads"], key_head_dim=la["head_dim"],
            value_head_dim=la["head_dim"],
            conv_kernel=la["short_conv_kernel_size"],
            gate_rank=la["head_dim"],
            neg_eigval=config["kda_allow_neg_eigval"],
        ),
        attn_output_gate=config["use_gqa_gate"],
        use_rope=config["use_rope"],
    )


# -- 2. the seeded leaves --------------------------------------------------------
def sizes(config: dict) -> dict:
    heads, D = config["num_attention_heads"], config["head_dim"]
    la = config["linear_attn_config"]
    LH, LD = la["num_heads"], la["head_dim"]
    fe = config["moe_intermediate_size"]
    return {
        "d": config["hidden_size"], "v": config["vocab_size"],
        "L": config["num_hidden_layers"],
        "period": config["gqa_interval"] + 1,
        "H": heads, "K": config["num_key_value_heads"], "D": D,
        "q": heads * D, "kv": config["num_key_value_heads"] * D,
        "LH": LH, "LD": LD, "lk": LH * LD, "lv": LH * LD, "r": LD,
        "cw": la["short_conv_kernel_size"], "C": 3 * LH * LD,
        "fe": fe, "fs": fe * config["n_shared_experts"],
        "E": config["n_routed_experts"],
        "Er": config.get("source_n_routed_experts",
                         config["n_routed_experts"]),
        "first": config.get("first_expert_held", 0),
        "k": config["num_experts_per_tok"],
    }


def stacks(sz: dict) -> tuple:
    """The model's contiguous runs of like layers, in order: of each period
    its attention layer, then its linear layers. The keys are the run
    layout ``models.llama.stack_layer_runs`` takes (``<stack>:<period>:<run
    key>``), which the engine stacks by period at construction."""
    out = []
    for p in range(sz["L"] // sz["period"]):
        first = p * sz["period"]
        out.append((f"moe_layers:{p}:r0_attn", "gqa", first, 1))
        out.append((f"moe_layers:{p}:r1_linear", "linear", first + 1,
                    sz["period"] - 1))
    return tuple(out)


def layer_leaves(root, kind: str, layer, sz: dict) -> dict:
    """One layer as served: ``name -> (q, scale)`` or an array."""
    def matrix(name, a, b):
        return W.matrix(root, LEAF_NO[name], layer, sz[a], sz[b])

    def key(name):
        return W.key(root, LEAF_NO[name], layer)

    out = {name: matrix(name, a, b)
           for name, (a, b) in (GQA if kind == "gqa" else LINEAR).items()}
    out.update({name: W.norm(root, LEAF_NO[name], layer, sz[n])
                for name, n in NORMS.items()})
    if kind == "linear":
        out["o_norm"] = W.norm(root, LEAF_NO["o_norm"], layer, sz["LD"])
        out["conv"] = (W.float_matrix(
            root, LEAF_NO["conv"], layer, sz["cw"], sz["C"])
        ).astype(jnp.bfloat16)
        # decay rates exp(a_log) in 1..16 and softplus offsets for steps of
        # 0.001..0.1, log-uniform: the KDA / Mamba2 initialisation
        out["a_log"] = jnp.log(jax.random.uniform(
            key("a_log"), (sz["LH"],), minval=1.0, maxval=16.0))
        dt = jnp.exp(jax.random.uniform(
            key("dt_bias"), (sz["lk"],),
            minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
        out["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))   # softplus^-1(dt)
    out.update({name: matrix(name, a, b) for name, (a, b) in SHARED.items()})
    out.update({
        name: W.matrices(root, LEAF_NO[name], layer, sz["E"], sz[a], sz[b])
        for name, (a, b) in EXPERTS.items()})
    out["router"] = W.float_matrix(
        root, LEAF_NO["router"], layer, sz["d"], sz["Er"])
    out["router_bias"] = W.vector(
        root, LEAF_NO["router_bias"], layer, sz["Er"], 0.0, 0.05
    ).astype(jnp.float32)
    return out


# -- 3. the reference's call -----------------------------------------------------
def position_tables(ref, length: int, config: dict, sz: dict):
    return ()       # no positional embedding (use_rope false)


def apply_layer(ref, kind: str, seq, w: dict, tables, config: dict, sz: dict):
    """One layer of ``kind`` on one whole sequence [T, d] float32, at the
    share of the experts this configuration holds."""
    return ref.layer(
        seq, w, kind=kind, heads=sz["H"], kv_heads=sz["K"],
        linear_heads=sz["LH"], top_k=sz["k"],
        scale=config["routed_scaling_factor"], eps=config["rms_norm_eps"],
        neg_eigval=config["kda_allow_neg_eigval"],
        held=(sz["first"], sz["E"]))


# -- 4. the bytes of a step ------------------------------------------------------
def _matrix_bytes(sz: dict, table: dict, w: int, count: int = 1) -> int:
    """int8 matrices with a float32 scale for each output channel."""
    return count * sum(sz[a] * sz[b] * w + sz[b] * 4 for a, b in table.values())


def _layers(sz: dict) -> tuple[int, int]:
    """(attention layers, linear-attention layers)."""
    periods = sz["L"] // sz["period"]
    return periods, periods * (sz["period"] - 1)


def moe_experts_floor_bytes(config: dict, experts: float) -> float:
    """Least bytes the routed experts' matmuls of ONE layer move when
    ``experts`` distinct held experts have work: each one's three int8
    matrices and scales once (its tokens' activations are not counted)."""
    s = sizes(config)
    return experts * _matrix_bytes(
        s, EXPERTS, BYTES[config["precision"]["weights"]])


def state_row_bytes(config: dict) -> int:
    """What ONE linear layer keeps of one sequence: the float32 state and
    the conv tail in the compute type."""
    s = sizes(config)
    return (s["LH"] * s["LD"] * s["LD"] * 4
            + (s["cw"] - 1) * s["C"] * BYTES[config["precision"]["compute"]])


def lin_scan_floor_bytes(config: dict, rows: float) -> float:
    """Least bytes the delta-rule update moves in a pass over ``rows``
    sequences: each one's state and conv tail read and written once in
    every linear layer (q, k, v and the gates are not counted)."""
    return 2 * rows * state_row_bytes(config) * _layers(sizes(config))[1]


def weight_bytes(config: dict) -> int:
    """Bytes of one pass over the stack and the head: every layer's mixer,
    router and shared expert, and of the routed experts held here those a
    pass of the engine's rows can reach (rows x top-k assignments, or all
    that are held), int8 with float32 scales; float32 router and decay
    vectors, norms and the conv in the compute type."""
    s = sizes(config)
    w = BYTES[config["precision"]["weights"]]
    vec = BYTES[config["precision"]["compute"]]
    n_attn, n_lin = _layers(s)
    reach = min(s["E"], config["engine"]["max_batch_size"] * s["k"])
    moe = (_matrix_bytes(s, SHARED, w) + _matrix_bytes(s, EXPERTS, w, reach)
           + 4 * (s["d"] * s["Er"] + s["Er"]) + 2 * s["d"] * vec)
    gqa = _matrix_bytes(s, GQA, w)
    linear = (_matrix_bytes(s, LINEAR, w)
              + vec * (s["cw"] * s["C"] + s["LD"])
              + 4 * (s["LH"] + s["lk"]))
    head = s["d"] * s["v"] * w + s["v"] * 4 + s["d"] * vec
    return n_attn * gqa + n_lin * linear + s["L"] * moe + head


def kv_token_bytes(config: dict) -> int:
    """Bytes of one resident token's keys and values: the attention layers
    only (a linear layer keeps a state, whatever the length)."""
    s = sizes(config)
    return 2 * _layers(s)[0] * s["kv"] * BYTES[config["precision"]["kv_pages"]]


def step_floor_bytes(config: dict, resident_tokens: float,
                     step_tokens: float = 0.0) -> float:
    """Weights once, the resident keys and values once, the running rows'
    recurrent state read and written once, and the embedding rows of the
    tokens the step carries."""
    embed = (step_tokens * config["hidden_size"]
             * BYTES[config["precision"]["compute"]])
    return (weight_bytes(config)
            + resident_tokens * kv_token_bytes(config)
            + lin_scan_floor_bytes(config, config["engine"]["max_batch_size"])
            + embed)
