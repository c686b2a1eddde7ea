"""Seeded weights of a Qwen2-shaped decoder, in the types they are served in.

Every leaf is a pure function of (seed, leaf, layer): the server builds the
whole stacked tree on the device in one jitted call, and the reference asks
for one layer at a time and gets the same values. Nothing here imports the
program.

Large matrices are int8 with one float32 scale per output channel (the
configuration's stated weight precision); what a weight "is" is exactly
``q * scale``, so the float32 reference holds the same model the program
serves and differs from it only by the program's arithmetic. Scales vary by
channel, norm weights vary around one and the QKV biases are not zero, so
that an axis mix-up or a dropped bias shows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# (leaf, fan-in size key, fan-out size key); sizes come from ``sizes()``.
MATRICES = {
    "wq": ("d", "q"), "wk": ("d", "kv"), "wv": ("d", "kv"), "wo": ("q", "d"),
    "wg": ("d", "f"), "wu": ("d", "f"), "wd": ("f", "d"),
}
BIASES = {"bq": "q", "bk": "kv", "bv": "kv"}
NORMS = ("attn_norm", "mlp_norm")
_LEAF_NO = {name: i for i, name in enumerate(
    [*MATRICES, *BIASES, *NORMS, "embed", "final_norm", "lm_head"]
)}
INT4_GROUP = 128


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


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def _key(root, leaf: str, layer) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(root, _LEAF_NO[leaf]), layer)


def matrix(root, leaf: str, layer, n_in: int, n_out: int):
    """(int8 [n_in, n_out] uniform in -127..127, float32 scale [n_out])
    with the dequantized standard deviation near fan_in ** -0.5."""
    kq, ks = jax.random.split(_key(root, leaf, layer))
    # Four bytes from each 32-bit word (a uint8 draw spends a word on each),
    # taken by shifts into four blocks of rows: splitting the last axis
    # instead pads a minor dimension of 4 to a whole tile on the TPU.
    words = jax.random.bits(kq, (n_in // 4, n_out), jnp.uint32)
    bits = jnp.concatenate([(words >> s) & 0xFF for s in (0, 8, 16, 24)])
    q = (bits.astype(jnp.int32) % 255 - 127).astype(jnp.int8)
    base = float(n_in) ** -0.5 * 3.0**0.5 / 127.0
    scale = base * (0.75 + 0.5 * jax.random.uniform(ks, (n_out,)))
    return q, scale.astype(jnp.float32)


def vector(root, leaf: str, layer, n: int, mean: float, std: float):
    """A bfloat16 norm weight or bias."""
    x = mean + std * jax.random.normal(_key(root, leaf, layer), (n,))
    return x.astype(jnp.bfloat16)


def embedding(root, v: int, d: int):
    return jax.random.normal(_key(root, "embed", 0), (v, d), jnp.bfloat16)


def bias(root, leaf: str, layer, sz: dict):
    return vector(root, leaf, layer, sz[BIASES[leaf]], 0.0, 0.1)


def norm(root, leaf: str, layer, sz: dict):
    return vector(root, leaf, layer, sz["d"], 1.0, 0.1)


def layer_leaves(root, layer, sz: dict) -> dict:
    """One layer as served: ``name -> (q, scale)`` or a bfloat16 vector."""
    out = {
        name: matrix(root, name, layer, sz[a], sz[b])
        for name, (a, b) in MATRICES.items()
    }
    out.update({name: bias(root, name, layer, sz) for name in BIASES})
    out.update({name: norm(root, name, layer, sz) for name in NORMS})
    return out


def dequantize(q, scale, weight_bits: int = 8):
    """float32 matrix of an int8 leaf. ``weight_bits=4`` is the control:
    the same matrix rounded to symmetric int4 in groups of 128 rows of the
    contraction axis, as a group-wise int4 checkpoint would hold it."""
    w = q.astype(jnp.float32) * scale[None, :]
    if weight_bits == 8:
        return w
    if weight_bits != 4:
        raise ValueError(f"weight_bits={weight_bits}: 8 or 4")
    n_in, n_out = w.shape
    group = INT4_GROUP if n_in % INT4_GROUP == 0 else n_in
    g = w.reshape(n_in // group, group, n_out)
    step = jnp.max(jnp.abs(g), axis=1, keepdims=True) / 7.0
    step = jnp.where(step > 0, step, 1.0)
    return (jnp.clip(jnp.round(g / step), -7, 7) * step).reshape(n_in, n_out)
