"""The dense Jamba family (ai21labs/AI21-Jamba2-3B, ``num_experts`` 1) as the
harness needs it: pre-norm layers in periods of ``attn_layer_period``, one
attention layer at ``attn_layer_offset`` (grouped-query, one kv head in the
3B, no rotary embedding) and Mamba-1 selective-scan layers otherwise (Jamba's
RMSNorm on ``dt``, ``B`` and ``C``), SwiGLU MLPs. Nothing of the model is
cut: the file holds every published key.

The published head is tied to the embedding. The harness draws ``embed``
(bfloat16) and ``lm_head`` (int8) apart for every family and its reference's
head reads ``lm_head`` (``server.tree_builder``, ``check.head_logits``), so
the program's model here has a head of its own of the same ``[hidden,
vocab]`` (preset ``jamba2-3b-untied``; the file keeps ``tie_word_embeddings:
true`` and says so under ``assumed``).

A configuration's file names its family (``"family": "jamba"``) and
``loading.load_family`` finds this module by that name. The mathematics is
in ``reference/jamba.py``, which this module only calls. Beside what
``loading.FAMILY_ANSWERS`` lists it keeps ``ssm_scan_floor_bytes``, the
least bytes of the selective scan, which ``kernels.ssm_scan_hbm_share``
reads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks import weights as W
from benchmarks.bytes_model import BYTES

# Nothing of this family's model may differ from its preset.
REDUCED: dict = {}

# Scope names this family's program adds to ``scope_reduce.SCOPES``: the
# three with a ``kernels.ssm_*_ms`` reader.
SCOPES = ("ssm_scan", "ssm_proj", "state_io")

# (leaf, fan-in size key, fan-out size key) of the 2-D int8 matrices
ATTENTION = {"wq": ("d", "q"), "wk": ("d", "kv"), "wv": ("d", "kv"),
             "wo": ("q", "d")}
MAMBA = {"m_in": ("d", "di2"), "m_x": ("di", "xp"), "m_dt": ("r", "di"),
         "m_out": ("di", "d")}
MLP = {"wg": ("d", "f"), "wu": ("d", "f"), "wd": ("f", "d")}
NORMS = {"attn_norm": "d", "mlp_norm": "d"}
SMALL_NORMS = {"dt_norm": "r", "b_norm": "ds", "c_norm": "ds"}
LEAF_NO = {name: i for i, name in enumerate([
    *ATTENTION, *MAMBA, *MLP, *NORMS, *SMALL_NORMS, "conv", "conv_b",
    "a_log", "dt_bias", "d_skip", "embed", "final_norm", "lm_head",
])}


def _period(config: dict) -> tuple:
    """One period of the layer pattern as kinds: attention where ``i %
    attn_layer_period == attn_layer_offset`` (the model type's own rule)."""
    return tuple(
        "attention" if i == config["attn_layer_offset"] else "mamba"
        for i in range(config["attn_layer_period"]))


# -- 1. the program's model ------------------------------------------------------
def model_config(config: dict):
    from opsagent_tpu.models.config import MambaConfig, ModelConfig

    if config["num_experts"] != 1:
        raise ValueError("jamba: the family serves the dense models only")
    rank = config["mamba_dt_rank"]
    d = config["hidden_size"]
    return ModelConfig(
        name=config["preset"],
        vocab_size=config["vocab_size"],
        hidden_size=d,
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        rms_norm_eps=config["rms_norm_eps"],
        # the harness's head is its own leaf (module header)
        tie_embeddings=False,
        max_position=config["max_position_embeddings"],
        mixer_period=tuple(
            "attn" if kind == "attention" else kind
            for kind in _period(config)),
        mamba=MambaConfig(
            d_inner=config["mamba_expand"] * d,
            d_state=config["mamba_d_state"],
            d_conv=config["mamba_d_conv"],
            dt_rank=-(-d // 16) if rank == "auto" else rank,
            conv_bias=config["mamba_conv_bias"],
        ),
        use_rope=False,
    )


# -- 2. the seeded leaves --------------------------------------------------------
def sizes(config: dict) -> dict:
    d, heads = config["hidden_size"], config["num_attention_heads"]
    if not config["mamba_conv_bias"] or config["mamba_proj_bias"]:
        raise ValueError("jamba: the leaf tables take a conv bias and no "
                         "projection bias")
    D = d // heads
    di, ds = config["mamba_expand"] * d, config["mamba_d_state"]
    r = config["mamba_dt_rank"]
    return {
        "d": d, "v": config["vocab_size"], "L": config["num_hidden_layers"],
        "f": config["intermediate_size"], "period": _period(config),
        "H": heads, "K": config["num_key_value_heads"], "D": D,
        "q": heads * D, "kv": config["num_key_value_heads"] * D,
        "di": di, "di2": 2 * di, "ds": ds, "dc": config["mamba_d_conv"],
        "r": r, "xp": r + 2 * ds,
    }


def _runs(sz: dict) -> list:
    """One period as runs of like layers: [[kind, layers], ...]."""
    runs = []
    for kind in sz["period"]:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return runs


def stacks(sz: dict) -> tuple:
    """The model's contiguous runs of like layers, in order. The keys are
    the run layout ``models.llama.stack_layer_runs`` takes
    (``<stack>:<period>:<run key>``, the run keys ``period_runs`` gives:
    ``r<i>_<mixer>`` with the program's mixer names), which the engine
    stacks by period at construction."""
    out = []
    for p in range(sz["L"] // len(sz["period"])):
        first = p * len(sz["period"])
        for i, (kind, n) in enumerate(_runs(sz)):
            mixer = "attn" if kind == "attention" else kind
            out.append((f"layers:{p}:r{i}_{mixer}", kind, first, n))
            first += n
    return tuple(out)


def layer_leaves(root, kind: str, layer, sz: dict) -> dict:
    """One layer as served: ``name -> (q, scale)`` or an array."""
    def key(name):
        return W.key(root, LEAF_NO[name], layer)

    table = {**(ATTENTION if kind == "attention" else MAMBA), **MLP}
    out = {name: W.matrix(root, LEAF_NO[name], layer, sz[a], sz[b])
           for name, (a, b) in table.items()}
    out.update({name: W.norm(root, LEAF_NO[name], layer, sz[n])
                for name, n in NORMS.items()})
    if kind == "attention":
        return out
    out.update({name: W.norm(root, LEAF_NO[name], layer, sz[n])
                for name, n in SMALL_NORMS.items()})
    di, ds = sz["di"], sz["ds"]
    out["conv"] = W.float_matrix(
        root, LEAF_NO["conv"], layer, sz["dc"], di).astype(jnp.bfloat16)
    out["conv_b"] = W.vector(root, LEAF_NO["conv_b"], layer, di, 0.0, 0.1)
    # Mamba's own initialisation, held [d_state, d_inner] as the program
    # holds the state: A = -(1..d_state) along the state axis (S4D-real)
    # times a seeded jitter of a tenth either way, a skip around one, and
    # softplus offsets for steps of 0.001..0.1, log-uniform, one a channel;
    # float32, as the state. (Uniform draws, as ``families/olmo_hybrid.py``'s:
    # a float32 normal draw rounds apart by a unit in the last place between
    # the server's batched program and the reference's, which a bfloat16
    # leaf hides and a float32 leaf does not.)
    out["a_log"] = jnp.log(
        jnp.arange(1, ds + 1, dtype=jnp.float32)[:, None]
        * jax.random.uniform(key("a_log"), (ds, di), minval=0.9, maxval=1.1))
    out["d_skip"] = jax.random.uniform(
        key("d_skip"), (di,), minval=0.8, maxval=1.2)
    dt = jnp.exp(jax.random.uniform(
        key("dt_bias"), (di,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    out["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))       # softplus^-1(dt)
    return out


# -- 3. the reference's call -----------------------------------------------------
def position_tables(ref, length: int, config: dict, sz: dict):
    return ()       # no positional embedding anywhere


def apply_layer(ref, kind: str, seq, w: dict, tables, config: dict, sz: dict):
    """One layer of ``kind`` on one whole sequence [T, d] float32."""
    return ref.layer(seq, w, kind=kind, heads=sz["H"], kv_heads=sz["K"],
                     eps=config["rms_norm_eps"])


# -- 4. the bytes of a step ------------------------------------------------------
def _matrix_bytes(sz: dict, table: dict, w: int) -> int:
    """int8 matrices with a float32 scale for each output channel."""
    return sum(sz[a] * sz[b] * w + sz[b] * 4 for a, b in table.values())


def _layers(sz: dict) -> tuple[int, int]:
    """(attention layers, Mamba layers)."""
    periods = sz["L"] // len(sz["period"])
    full = sum(1 for kind in sz["period"] if kind == "attention")
    return periods * full, periods * (len(sz["period"]) - full)


def state_row_bytes(config: dict) -> int:
    """What ONE Mamba layer keeps of one sequence: the float32 state and
    the conv tail in the compute type."""
    s = sizes(config)
    return (s["ds"] * s["di"] * 4
            + (s["dc"] - 1) * s["di"] * BYTES[config["precision"]["compute"]])


def ssm_scan_floor_bytes(config: dict, rows: float) -> float:
    """Least bytes the selective scan moves in a pass over ``rows``
    sequences: each one's state and conv tail read and written once in
    every Mamba layer (x, dt, B and C are not counted), whatever
    implements the scan."""
    return 2 * rows * state_row_bytes(config) * _layers(sizes(config))[1]


def weight_bytes(config: dict) -> int:
    """Bytes of one pass over the stack and the head: every layer's mixer
    and MLP, int8 with float32 scales; norms, the conv and its bias in the
    compute type, float32 ``A_log``, ``D`` and the dt bias; the harness's
    int8 head."""
    s = sizes(config)
    w = BYTES[config["precision"]["weights"]]
    vec = BYTES[config["precision"]["compute"]]
    n_full, n_mamba = _layers(s)
    mlp = _matrix_bytes(s, MLP, w) + 2 * s["d"] * vec
    full = _matrix_bytes(s, ATTENTION, w)
    mamba = (_matrix_bytes(s, MAMBA, w)
             + vec * ((s["dc"] + 1) * s["di"] + s["r"] + 2 * s["ds"])
             + 4 * (s["ds"] * s["di"] + 2 * s["di"]))
    head = s["d"] * s["v"] * w + s["v"] * 4 + s["d"] * vec
    return n_full * full + n_mamba * mamba + s["L"] * mlp + head


def kv_token_bytes(config: dict) -> int:
    """Bytes of one resident token's keys and values: the attention layers
    only (a Mamba layer keeps a state, whatever the length)."""
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
            + ssm_scan_floor_bytes(config, config["engine"]["max_batch_size"])
            + embed)
