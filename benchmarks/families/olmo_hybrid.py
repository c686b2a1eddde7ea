"""The Olmo-Hybrid decoder family (allenai/Olmo-Hybrid-7B) as the harness
needs it: dense post-norm layers in periods of three gated delta-rule
linear-attention layers (one decay a head, key dim 96 and value dim 192,
full-rank gates) and one full-attention layer LAST (a kv head for every
head, RMSNorm over the whole q and k, no rotary embedding), SwiGLU MLPs, an
untied head. Nothing of the model is cut: the file holds every published key.

A configuration's file names its family (``"family": "olmo_hybrid"``) and
``loading.load_family`` finds this module by that name. The mathematics is in
``reference/olmo_hybrid.py``, which this module only calls. Beside what
``loading.FAMILY_ANSWERS`` lists it keeps ``lin_scan_floor_bytes``, the least
bytes of the state update, which ``kernels.gdn_scan_hbm_share`` reads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import weights as W
from benchmarks.bytes_model import BYTES

# Nothing of this family's model may differ from its preset.
REDUCED: dict = {}

# Scope names this family's program adds to ``scope_reduce.SCOPES``: the
# three with a ``kernels.gdn_*_ms`` reader.
SCOPES = ("lin_scan", "lin_proj", "state_io")

LAYER_TYPES = {"linear_attention": "linear", "full_attention": "full"}

# (leaf, fan-in size key, fan-out size key) of the 2-D int8 matrices; a
# layer's are drawn in two goes, ``from_d`` and ``to_d`` (``_cut``)
FULL = {"wq": ("d", "q"), "wk": ("d", "q"), "wv": ("d", "q"),
        "wo": ("q", "d")}
LINEAR = {"lq": ("d", "lk"), "lk": ("d", "lk"), "lv": ("d", "lv"),
          "lo": ("lv", "d"), "wa": ("d", "LH"), "wb": ("d", "LH"),
          "wog": ("d", "lv")}
MLP = {"wg": ("d", "f"), "wu": ("d", "f"), "wd": ("f", "d")}
NORMS = {"attn_norm": "d", "mlp_norm": "d"}
LEAF_NO = {name: i for i, name in enumerate([
    "from_d", "to_d", *NORMS, "qn", "kn", "o_norm", "conv", "a_log",
    "dt_bias", "embed", "final_norm", "lm_head",
])}


def _period(config: dict) -> tuple:
    """One period of ``layer_types`` as kinds, e.g. ("linear", "linear",
    "linear", "full"): the shortest prefix the whole list repeats."""
    types = [LAYER_TYPES[t] for t in config["layer_types"]]
    types = (types * config["num_hidden_layers"])[:config["num_hidden_layers"]]
    n = len(types)
    return tuple(next(
        types[:p] for p in range(1, n + 1)
        if n % p == 0 and types == types[:p] * (n // p)))


# -- 1. the program's model ------------------------------------------------------
def model_config(config: dict):
    from opsagent_tpu.models.config import LinearAttnConfig, ModelConfig

    theta = config["rope_parameters"]["rope_theta"]
    return ModelConfig(
        name=config["preset"],
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        rope_theta=ModelConfig.rope_theta if theta is None else float(theta),
        rms_norm_eps=config["rms_norm_eps"],
        attn_bias=config["attention_bias"],
        qk_norm=True,
        qk_norm_whole=True,
        tie_embeddings=config["tie_word_embeddings"],
        max_position=config["max_position_embeddings"],
        mixer_period=tuple(
            "attn" if kind == "full" else kind for kind in _period(config)),
        linear_attn=LinearAttnConfig(
            num_heads=config["linear_num_value_heads"],
            key_head_dim=config["linear_key_head_dim"],
            value_head_dim=config["linear_value_head_dim"],
            conv_kernel=config["linear_conv_kernel_dim"],
            gate_rank=0,
            neg_eigval=config["linear_allow_neg_eigval"],
            decay="head", gates="full",
        ),
        use_rope=theta is not None,
        post_norm=True,
    )


# -- 2. the seeded leaves --------------------------------------------------------
def sizes(config: dict) -> dict:
    heads = config["num_attention_heads"]
    if config["num_key_value_heads"] != heads:
        raise ValueError("olmo_hybrid: the leaf tables take a kv head a head")
    LH = config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    return {
        "d": config["hidden_size"], "v": config["vocab_size"],
        "L": config["num_hidden_layers"], "f": config["intermediate_size"],
        "period": _period(config),
        "H": heads, "q": config["hidden_size"],
        "LH": LH, "dk": dk, "dv": dv, "lk": LH * dk, "lv": LH * dv,
        "cw": config["linear_conv_kernel_dim"], "C": LH * (2 * dk + dv),
    }


def stacks(sz: dict) -> tuple:
    """The model's contiguous runs of like layers, in order. The keys are
    the run layout ``models.llama.stack_layer_runs`` takes
    (``<stack>:<period>:<run key>``, the run keys ``period_runs`` gives:
    ``r<i>_<mixer>`` with the program's mixer names), which the engine
    stacks by period at construction."""
    runs = []
    for kind in sz["period"]:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    out = []
    for p in range(sz["L"] // len(sz["period"])):
        first = p * len(sz["period"])
        for i, (kind, n) in enumerate(runs):
            mixer = "attn" if kind == "full" else kind
            out.append((f"layers:{p}:r{i}_{mixer}", kind, first, n))
            first += n
    return tuple(out)


def _cut(root, leaf: str, layer, names: tuple, table: dict, sz: dict) -> dict:
    """The matrices ``names``, which share a side of length ``d``, cut out
    of ONE draw along the other side: ``W.matrix`` costs the chip's
    compiler about a second a call whatever its size, and a draw for each of
    a layer's 7-10 matrices in each of the 16 runs ``tree_builder`` maps was
    157 s of a first run's set-up, and is 39 s so (compile, PR 33). Each
    keeps the standard deviation ``W.matrix`` would give it alone (fan-in **
    -0.5)."""
    from_d = table[names[0]][0] == "d"
    widths = [sz[table[n][1 if from_d else 0]] for n in names]
    total = sum(widths)
    q, scale = W.matrix(root, LEAF_NO[leaf], layer,
                        sz["d"] if from_d else total,
                        total if from_d else sz["d"])
    out, at = {}, 0
    for i, (name, n) in enumerate(zip(names, widths)):
        if from_d:
            out[name] = (q[:, at:at + n], scale[at:at + n])
        else:
            # its own scales, at its own fan-in, by ``W.matrix``'s expression
            # (a factor on the draw's would round apart in two programs)
            base = float(n) ** -0.5 * 3.0**0.5 / 127.0
            own = base * (0.75 + 0.5 * jax.random.uniform(
                jax.random.fold_in(W.key(root, LEAF_NO[leaf], layer), i),
                (sz["d"],)))
            out[name] = (q[at:at + n], own.astype(jnp.float32))
        at += n
    return out


def layer_leaves(root, kind: str, layer, sz: dict) -> dict:
    """One layer as served: ``name -> (q, scale)`` or an array."""
    def key(name):
        return W.key(root, LEAF_NO[name], layer)

    def norm(name, n):
        return W.norm(root, LEAF_NO[name], layer, n)

    table = {**(FULL if kind == "full" else LINEAR), **MLP}
    # the widest first, so that all but the last narrow ones start on a tile
    reads = tuple(sorted((n for n, (a, _) in table.items() if a == "d"),
                         key=lambda n: -sz[table[n][1]]))
    writes = tuple(n for n, (a, _) in table.items() if a != "d")
    out = {**_cut(root, "from_d", layer, reads, table, sz),
           **_cut(root, "to_d", layer, writes, table, sz)}
    out.update({name: norm(name, sz[n]) for name, n in NORMS.items()})
    if kind == "full":
        out["qn"], out["kn"] = norm("qn", sz["q"]), norm("kn", sz["q"])
        return out
    out["o_norm"] = norm("o_norm", sz["dv"])
    out["conv"] = W.float_matrix(
        root, LEAF_NO["conv"], layer, sz["cw"], sz["C"]).astype(jnp.bfloat16)
    # decay rates exp(a_log) in 1..16 and softplus offsets for steps of
    # 0.001..0.1, log-uniform, one of each a head: the Gated DeltaNet /
    # Mamba2 initialisation
    out["a_log"] = jnp.log(jax.random.uniform(
        key("a_log"), (sz["LH"],), minval=1.0, maxval=16.0))
    dt = jnp.exp(jax.random.uniform(
        key("dt_bias"), (sz["LH"],),
        minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    out["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1(dt)
    return out


# -- 3. the reference's call -----------------------------------------------------
def position_tables(ref, length: int, config: dict, sz: dict):
    if config["rope_parameters"]["rope_theta"] is not None:
        raise ValueError("olmo_hybrid: the reference has no rotary embedding")
    return ()


def apply_layer(ref, kind: str, seq, w: dict, tables, config: dict, sz: dict):
    """One layer of ``kind`` on one whole sequence [T, d] float32."""
    return ref.layer(
        seq, w, kind=kind, heads=sz["H"], linear_heads=sz["LH"],
        eps=config["rms_norm_eps"],
        neg_eigval=config["linear_allow_neg_eigval"])


# -- 4. the bytes of a step ------------------------------------------------------
def _matrix_bytes(sz: dict, table: dict, w: int) -> int:
    """int8 matrices with a float32 scale for each output channel."""
    return sum(sz[a] * sz[b] * w + sz[b] * 4 for a, b in table.values())


def _layers(sz: dict) -> tuple[int, int]:
    """(full-attention layers, linear-attention layers)."""
    periods = sz["L"] // len(sz["period"])
    full = sum(1 for kind in sz["period"] if kind == "full")
    return periods * full, periods * (len(sz["period"]) - full)


def state_row_bytes(config: dict) -> int:
    """What ONE linear layer keeps of one sequence: the float32 state and
    the conv tail in the compute type."""
    s = sizes(config)
    return (s["LH"] * s["dk"] * s["dv"] * 4
            + (s["cw"] - 1) * s["C"] * BYTES[config["precision"]["compute"]])


def lin_scan_floor_bytes(config: dict, rows: float) -> float:
    """Least bytes the delta-rule update moves in a pass over ``rows``
    sequences: each one's state and conv tail read and written once in
    every linear layer (q, k, v and the gates are not counted)."""
    return 2 * rows * state_row_bytes(config) * _layers(sizes(config))[1]


def weight_bytes(config: dict) -> int:
    """Bytes of one pass over the stack and the head: every layer's mixer
    and MLP, int8 with float32 scales; norms and the conv in the compute
    type, float32 ``A_log`` and ``dt_bias``."""
    s = sizes(config)
    w = BYTES[config["precision"]["weights"]]
    vec = BYTES[config["precision"]["compute"]]
    n_full, n_lin = _layers(s)
    mlp = _matrix_bytes(s, MLP, w) + 2 * s["d"] * vec
    full = _matrix_bytes(s, FULL, w) + 2 * s["q"] * vec
    linear = (_matrix_bytes(s, LINEAR, w)
              + vec * (s["cw"] * s["C"] + s["dv"]) + 4 * 2 * s["LH"])
    head = s["d"] * s["v"] * w + s["v"] * 4 + s["d"] * vec
    return n_full * full + n_lin * linear + s["L"] * mlp + head


def kv_token_bytes(config: dict) -> int:
    """Bytes of one resident token's keys and values: the full-attention
    layers only (a linear layer keeps a state, whatever the length)."""
    s = sizes(config)
    return 2 * _layers(s)[0] * s["q"] * BYTES[config["precision"]["kv_pages"]]


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
