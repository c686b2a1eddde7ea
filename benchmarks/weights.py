"""Seeded weights of a decoder, in the types they are served in.

Every leaf is a pure function of (seed, leaf number, layer): the server
builds the whole stacked tree on the device in one jitted call, and the
reference asks for one layer at a time and gets the same values. Which
leaves a layer has, their shapes and their numbers belong to the model's
family (``families/<family>.py``); what is here is true of every family.
Nothing here imports the program.

Large matrices are int8 with one float32 scale per output channel (the
configuration's stated weight precision); what a weight "is" is exactly
``q * scale``, so the float32 reference holds the same model the program
serves and differs from it only by the program's arithmetic. Scales vary by
channel, norm weights vary around one and biases are not zero, so that an
axis mix-up or a dropped bias shows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INT4_GROUP = 128


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def key(root, leaf_no: int, layer) -> jax.Array:
    """The key of one leaf of one layer; ``leaf_no`` is the family's number
    for the leaf (its ``LEAF_NO``), which never changes."""
    return jax.random.fold_in(jax.random.fold_in(root, leaf_no), layer)


def matrix(root, leaf_no: int, layer, n_in: int, n_out: int, part=None):
    """(int8 [n_in, n_out] uniform in -127..127, float32 scale [n_out])
    with the dequantized standard deviation near fan_in ** -0.5. ``part``
    tells apart the matrices of one leaf that a layer holds many of."""
    k = key(root, leaf_no, layer)
    kq, ks = jax.random.split(k if part is None else jax.random.fold_in(k, part))
    # Four bytes from each 32-bit word (a uint8 draw spends a word on each),
    # taken by shifts into four blocks of rows: splitting the last axis
    # instead pads a minor dimension of 4 to a whole tile on the TPU.
    words = jax.random.bits(kq, (n_in // 4, n_out), jnp.uint32)
    bits = jnp.concatenate([(words >> s) & 0xFF for s in (0, 8, 16, 24)])
    q = (bits.astype(jnp.int32) % 255 - 127).astype(jnp.int8)
    base = float(n_in) ** -0.5 * 3.0**0.5 / 127.0
    scale = base * (0.75 + 0.5 * jax.random.uniform(ks, (n_out,)))
    return q, scale.astype(jnp.float32)


def matrices(root, leaf_no: int, layer, n: int, n_in: int, n_out: int):
    """``n`` matrices of one leaf, stacked, as a layer holds its experts:
    (int8 [n, n_in, n_out], float32 scale [n, n_out]), made one at a time."""
    return jax.lax.map(
        lambda part: matrix(root, leaf_no, layer, n_in, n_out, part),
        jnp.arange(n, dtype=jnp.int32))


def float_matrix(root, leaf_no: int, layer, n_in: int, n_out: int):
    """A float32 matrix that is served unquantized, as a router is."""
    x = jax.random.normal(key(root, leaf_no, layer), (n_in, n_out))
    return x * float(n_in) ** -0.5


def vector(root, leaf_no: int, layer, n: int, mean: float, std: float):
    """A bfloat16 norm weight or bias."""
    x = mean + std * jax.random.normal(key(root, leaf_no, layer), (n,))
    return x.astype(jnp.bfloat16)


def norm(root, leaf_no: int, layer, n: int):
    """A norm's weight, around one."""
    return vector(root, leaf_no, layer, n, 1.0, 0.1)


def embedding(root, leaf_no: int, v: int, d: int):
    return jax.random.normal(key(root, leaf_no, 0), (v, d), jnp.bfloat16)


def dequantize(q, scale, weight_bits: int = 8):
    """float32 matrix of an int8 leaf. ``weight_bits=4`` is the control:
    the same matrix rounded to symmetric int4 in groups of 128 rows of the
    contraction axis, as a group-wise int4 checkpoint would hold it.
    Stacked matrices (a leading axis) are taken one by one."""
    if q.ndim > 2:
        return jax.vmap(lambda a, b: dequantize(a, b, weight_bits))(q, scale)
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


def as_float32(leaf, weight_bits: int = 8):
    """A served leaf as the reference takes it: an int8 pair dequantized
    (or rounded to the control's bits), anything else widened."""
    if isinstance(leaf, tuple):
        return dequantize(*leaf, weight_bits=weight_bits)
    return leaf.astype(jnp.float32)
