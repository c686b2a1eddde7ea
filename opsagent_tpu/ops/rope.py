"""Rotary position embeddings (llama-family convention) with long-context
frequency scaling: Llama-3.1's "llama3" wavelength-banded interpolation
and YaRN (DeepSeek-V2/V3), including YaRN's mscale factor folded into the
cos/sin tables. Formulas mirror the HF reference implementations
(modeling_llama._compute_llama3_parameters, modeling_deepseek's yarn
rotary embedding) so scaled checkpoints reproduce their training-time
position encoding."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..utils.profiling import scoped


def yarn_get_mscale(scale: float, mscale: float) -> float:
    """YaRN attention-magnitude correction (HF yarn_get_mscale)."""
    if scale <= 1.0 or mscale == 0.0:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def _scaled_freqs(head_dim: int, theta: float, scaling) -> tuple[jnp.ndarray, float]:
    """(inverse frequencies [head_dim//2], cos/sin magnitude factor)."""
    half = head_dim // 2
    inv = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    if scaling is None:
        return inv, 1.0
    if scaling.rope_type == "llama3":
        # Wavelength-banded: low-frequency dims fully interpolated
        # (divided by factor), high-frequency dims untouched, smooth
        # ramp between (HF _compute_llama3_parameters).
        orig = float(scaling.original_max_position)
        wavelen = 2.0 * math.pi / inv
        low_wl = orig / scaling.low_freq_factor
        high_wl = orig / scaling.high_freq_factor
        smooth = (
            (orig / wavelen - scaling.low_freq_factor)
            / (scaling.high_freq_factor - scaling.low_freq_factor)
        )
        banded = jnp.where(
            wavelen > low_wl,
            inv / scaling.factor,
            jnp.where(
                wavelen < high_wl,
                inv,
                (1.0 - smooth) * inv / scaling.factor + smooth * inv,
            ),
        )
        return banded, 1.0
    if scaling.rope_type == "yarn":
        # NTK-by-parts: dims rotating faster than beta_fast at the
        # original window keep their frequency (extrapolation), dims
        # slower than beta_slow interpolate (divide by factor), linear
        # ramp between (HF yarn_find_correction_range / ramp mask).
        dim = head_dim
        orig = float(scaling.original_max_position)

        def correction_dim(num_rot: float) -> float:
            return (
                dim * math.log(orig / (num_rot * 2.0 * math.pi))
            ) / (2.0 * math.log(theta))

        low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
        high = min(math.ceil(correction_dim(scaling.beta_slow)), dim - 1)
        ramp = jnp.clip(
            (jnp.arange(half, dtype=jnp.float32) - low)
            / max(high - low, 1e-3),
            0.0, 1.0,
        )
        extrap_mask = 1.0 - ramp
        yarned = (
            inv / scaling.factor * (1.0 - extrap_mask) + inv * extrap_mask
        )
        att = yarn_get_mscale(
            scaling.factor, scaling.mscale
        ) / yarn_get_mscale(scaling.factor, scaling.mscale_all_dim)
        return yarned, att
    raise ValueError(f"unknown rope scaling type {scaling.rope_type!r}")


@scoped("attn_qkv")
def rope_table(
    positions: jax.Array, head_dim: int, theta: float, scaling=None
) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for given positions.

    positions: [..., S] int32 -> (cos, sin): [..., S, head_dim//2] f32.
    ``scaling`` is an optional ``config.RopeScalingConfig``.
    """
    freqs, att = _scaled_freqs(head_dim, theta, scaling)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # [..., S, half]
    return jnp.cos(angles) * att, jnp.sin(angles) * att


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs (x[..2i], x[..2i+1]) split as first/second half (the
    llama "rotate_half" convention used by HF checkpoints).

    x: [B, S, H, D]; cos/sin: [B, S, D//2] (broadcast over heads).
    """
    half = x.shape[-1] // 2
    x1 = x[..., :half]
    x2 = x[..., half:]
    c = cos[..., None, :]  # [B, S, 1, half]
    s = sin[..., None, :]
    out1 = x1 * c - x2 * s
    out2 = x2 * c + x1 * s
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)
