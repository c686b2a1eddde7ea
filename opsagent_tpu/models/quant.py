"""Weight-only int8 / int4 quantization for serving.

The decode step is HBM-bandwidth-bound: every step streams the full weight
set. Storing weights as int8 with per-output-channel symmetric scales
halves that traffic (and halves the footprint — Llama-3-8B drops from
~16 GB bf16, which does NOT fit a 16 GB v5e chip, to ~8 GB, which does);
int4 with per-(group, output-channel) scales halves it AGAIN (~4 GB),
roughly doubling the weight-streaming decode ceiling at the cost of more
rounding error (group-wise scaling — default group 128 along the
contraction axis — keeps that error local). Compute stays on the bf16
MXU path: XLA fuses the dequantize (intN -> bf16 multiply by scale) into
the matmul operand read, so there is no separate materialized
dequantized copy; int4 values travel two-nibbles-per-byte in
self-packed int8 (see QuantizedLinear4).

Design: a ``QuantizedLinear`` pytree leaf-pair {q: int8 [..., in, out],
scale: [..., out]} that the model's matmul helper (``llama._mm``)
dispatches on — model code is otherwise unchanged, and the quantized tree
shards with the same PartitionSpecs (the scale follows its weight's output
axis). Per-channel symmetric scaling keeps greedy decoding faithful
(weight-only int8 is the standard near-lossless serving configuration; no
activation quantization).

The reference has no counterpart — its "model" is a remote HTTPS API
(reference pkg/llms/openai.go:69); quantization is part of the in-tree
serving engine that replaces it.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


class QuantizedBase:
    """Common shell of the quantized-weight pytree leaves: {q, scale}
    pair, flattening, and array-like shape accessors. Model code
    dispatches on THIS class (``llama._mm``/``_ein``/``_dense_weight``),
    so adding a new width cannot silently miss a dispatch site."""

    def __init__(self, q: jax.Array, scale: jax.Array):
        self.q = q
        self.scale = scale

    def tree_flatten(self):
        return (self.q, self.scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return self.q.shape

    @property
    def ndim(self):
        return self.q.ndim

    def dequantize(self) -> jax.Array:  # pragma: no cover - abstract
        raise NotImplementedError


@jax.tree_util.register_pytree_node_class
class QuantizedLinear(QuantizedBase):
    """int8 weight [..., in, out] + per-output-channel float32 scale
    [..., 1, out]; acts as a matmul rhs."""

    def dequantize(self) -> jax.Array:
        return self.q.astype(self.scale.dtype) * self.scale


def quantize_weight(w: jax.Array) -> QuantizedLinear:
    """Symmetric per-output-channel int8: scale = absmax / 127 over the
    input (contraction) axis, which is axis -2 of our [in, out] layout.
    Scales stay float32 — they are [..., 1, out] (a few MB even at 8B),
    and a bf16 scale would add ~0.4% multiplicative error per channel on
    top of the int8 rounding."""
    absmax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127)
    return QuantizedLinear(q.astype(jnp.int8), scale.astype(jnp.float32))


INT4_GROUP = 128  # contraction-axis group size (GPTQ/AWQ convention)


@jax.tree_util.register_pytree_node_class
class QuantizedLinear4(QuantizedBase):
    """int4 weight + per-(contraction-group, output-channel) scale.

    ``q`` is int8 [..., in/2, out] with TWO int4 values packed per byte
    along the contraction axis (low nibble = even row, high nibble = odd
    row); ``scale`` is float32 [..., G, 1, out] with G = in // group.
    Self-packed int8 rather than native jnp.int4 because jit-argument
    resharding of sub-byte arrays recursively re-enters jit inside
    device_put (measured on-chip r04: RecursionError at the 8B int4
    bench's first prefill) — the byte-level HBM traffic is identical
    (4 bits/weight) and XLA fuses the unpack shifts into the consuming
    matmul's operand read. Dequantize unpacks, then reshapes the
    contraction axis into (G, group) so each group's scale broadcasts
    over its slice, exactly like the int8 path."""

    @property
    def shape(self):
        *lead, half, out = self.q.shape
        return (*lead, 2 * half, out)

    def dequantize(self) -> jax.Array:
        *lead, half, Out = self.q.shape
        In = 2 * half
        G = self.scale.shape[-3]
        # Arithmetic shifts sign-extend on int8: (p << 4) >> 4 recovers
        # the low nibble's signed value, p >> 4 the high nibble's.
        low = jax.lax.shift_right_arithmetic(
            jax.lax.shift_left(self.q, jnp.int8(4)), jnp.int8(4)
        )
        high = jax.lax.shift_right_arithmetic(self.q, jnp.int8(4))
        w = jnp.stack([low, high], axis=-2)  # [..., in/2, 2, out]
        w = w.astype(self.scale.dtype).reshape(*lead, G, In // G, Out)
        return (w * self.scale).reshape(*lead, In, Out)


def pack_int4(q: jax.Array) -> jax.Array:
    """[-8, 7]-valued int8 [..., in, out] -> packed int8 [..., in/2, out]
    (even rows in the low nibble, odd rows in the high). ``in`` must be
    even — every transformer contraction dim is."""
    *lead, In, Out = q.shape
    if In % 2:
        raise ValueError(f"int4 packing needs an even contraction dim, got {In}")
    q = q.astype(jnp.int8).reshape(*lead, In // 2, 2, Out)
    low = q[..., 0, :] & jnp.int8(0x0F)
    high = jax.lax.shift_left(q[..., 1, :], jnp.int8(4))
    return high | low


def _group_size(In: int, group: int) -> int:
    """Largest divisor of ``In`` that is <= ``group``: a contraction dim
    that 128 does not divide (e.g. 4544 -> 64) still gets fine-grained
    scales instead of silently collapsing to one whole-axis group (which
    is exactly the fidelity regime group-wise int4 exists to avoid).
    Dims with no divisor >= 16 fall back to the whole axis — per-element
    scales would cost more HBM than the int4 saves — with a warning."""
    for d in range(min(group, In), 0, -1):
        if In % d == 0:
            if d >= 16:
                return d
            break
    from ..utils.logger import get_logger

    get_logger("quant").warning(
        "int4 group scaling degraded to ONE whole-axis group for a "
        "%d-wide contraction axis (no divisor in [16, %d]); expect "
        "int8-without-groups-level rounding error on these weights",
        In, group,
    )
    return In


def quantize_weight4(w: jax.Array, group: int = INT4_GROUP) -> QuantizedLinear4:
    """Symmetric group-wise int4: the contraction axis splits into
    ``group``-sized slices (``_group_size`` adapts to non-multiple dims),
    scale = group absmax / 7, values clipped to the symmetric [-7, 7]
    range."""
    *lead, In, Out = w.shape
    g = _group_size(In, group) if group else In
    G = In // g
    wg = w.astype(jnp.float32).reshape(*lead, G, g, Out)
    absmax = jnp.max(jnp.abs(wg), axis=-2, keepdims=True)  # [..., G, 1, out]
    scale = jnp.where(absmax > 0, absmax / 7.0, 1.0)
    q = jnp.clip(jnp.round(wg / scale), -7, 7)
    return QuantizedLinear4(
        pack_int4(q.astype(jnp.int8).reshape(*lead, In, Out)),
        scale.astype(jnp.float32),
    )


# Weights worth quantizing: the big matmuls. Norm vectors, biases, and the
# f32 router stay exact (tiny, and routing is precision-sensitive).
_QUANT_KEYS = frozenset(
    {"wq", "wk", "wv", "wo", "wg", "wu", "wd",
     "eg", "eu", "ed", "sg", "su", "sd", "lm_head",
     # MLA projections (models/llama._mla_attn_block); the low-rank
     # norms stay exact like other norm vectors.
     "wdq", "wuq", "wdkv", "wkr", "wukv",
     # gated attention and linear-attention projections (models/llama
     # linear_block): the conv, decay vectors and norms stay exact.
     "wgate", "lq", "lk", "lv", "lo", "f_down", "f_up", "g_down", "g_up",
     "wb", "wa", "wog",
     # a Mamba layer's four projections (models/llama mamba_block): the
     # conv, its bias, A_log, D, the dt bias and the norms stay exact.
     "m_in", "m_x", "m_dt", "m_out"}
)


def quantize_params(
    params: dict[str, Any], mode: str = "int8"
) -> dict[str, Any]:
    """Quantize every large linear in the stacked param tree (embed stays
    in compute dtype: its gather reads one row per token, not the whole
    table, so intN would save little and cost a per-token dequant).
    ``mode``: "int8" (per-output-channel) or "int4" (group-wise).

    MUST run on host-resident weights for large models: the whole point
    is that the full-precision tree does not fit the chip — the engine
    loads/initializes under a CPU default device, quantizes there, and
    only then device_puts the quantized tree onto the mesh."""
    quant = {"int8": quantize_weight, "int4": quantize_weight4}[mode]

    def walk(tree: dict[str, Any]) -> dict[str, Any]:
        out: dict[str, Any] = {}
        for key, leaf in tree.items():
            if isinstance(leaf, dict):
                out[key] = walk(leaf)
            elif key in _QUANT_KEYS:
                out[key] = quant(leaf)
            else:
                out[key] = leaf
        return out

    return walk(params)


def quantize_specs(
    specs: dict[str, Any], mode: str = "int8"
) -> dict[str, Any]:
    """PartitionSpec tree STRUCTURALLY matching ``quantize_params``' output
    (quantized leaves become QuantizedLinear/QuantizedLinear4 nodes whose
    children are the weight's spec and the scale's spec, so jax.tree.map
    pairs them): the intN weight keeps its spec; the scale broadcasts
    over the contraction axis and shards with the weight's output axis.
    int4 group scales REPLICATE over the grouped contraction axis too —
    they are tiny (In/group x Out floats), and sharding G would impose a
    divisibility constraint on every (model dim, tp) pair."""

    def scale_spec(spec: P) -> P:
        # [..., in, out] weight -> [..., 1, out] scale: same rank; only
        # the -2 (contraction) entry must be unsharded.
        parts = list(spec)
        if len(parts) >= 2:
            parts[-2] = None
        return P(*parts)

    def scale_spec4(spec: P) -> P:
        # [..., in, out] weight -> [..., G, 1, out] scale: rank + 1, the
        # G and broadcast axes unsharded, out follows the weight.
        parts = list(spec)
        return P(*parts[:-2], None, None, parts[-1])

    def walk(tree: dict[str, Any]) -> dict[str, Any]:
        out: dict[str, Any] = {}
        for key, leaf in tree.items():
            if isinstance(leaf, dict):
                out[key] = walk(leaf)
            elif key in _QUANT_KEYS:
                if mode == "int4":
                    out[key] = QuantizedLinear4(leaf, scale_spec4(leaf))
                else:
                    out[key] = QuantizedLinear(leaf, scale_spec(leaf))
            else:
                out[key] = leaf
        return out

    return walk(specs)
