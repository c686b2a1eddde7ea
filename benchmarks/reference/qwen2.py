"""Plain reference of the Qwen2 decoder family (Qwen2 / Qwen2.5).

The published forward pass in straightforward ``jax.numpy`` float32 with
every matrix multiplication at ``highest`` precision: RMSNorm, Q/K/V
projections with bias, rotary embedding in the rotate-half convention,
grouped-query causal attention, SwiGLU feed-forward, final norm and an
untied output head. No cache, no batching, no kernels: one whole sequence
at a time, attention in blocks of query rows so that the scores fit.

It imports nothing of the program and takes its weights as plain float32
arrays with matrices laid out [in, out].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def rope_tables(n: int, head_dim: int, theta: float):
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def _rope(x, cos, sin):
    """x: [T, heads, D]; rotates (x[..., :D/2], x[..., D/2:]) pairs."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _attention(q, k, v):
    """Causal grouped-query attention. q: [T, H, D]; k, v: [T, K, D]."""
    T, H, D = q.shape
    K = k.shape[1]
    qg = q.reshape(T, K, H // K, D)
    pos = jnp.arange(T)
    out = []
    for start in range(0, T, QUERY_BLOCK):
        qb = qg[start:start + QUERY_BLOCK]
        scores = jnp.einsum("tkgd,skd->kgts", qb, k) * (D ** -0.5)
        seen = pos[None, :] <= pos[start:start + QUERY_BLOCK, None]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("kgts,skd->tkgd", probs, v))
    return jnp.concatenate(out, axis=0).reshape(T, H * D)


def layer(x, w: dict, cos, sin, *, heads: int, kv_heads: int, eps: float):
    """One decoder layer on a whole sequence. x: [T, d] float32."""
    with jax.default_matmul_precision("highest"):
        T = x.shape[0]
        h = _rms_norm(x, w["attn_norm"], eps)
        q = (h @ w["wq"] + w["bq"]).reshape(T, heads, -1)
        k = (h @ w["wk"] + w["bk"]).reshape(T, kv_heads, -1)
        v = (h @ w["wv"] + w["bv"]).reshape(T, kv_heads, -1)
        attn = _attention(_rope(q, cos, sin), _rope(k, cos, sin), v)
        x = x + attn @ w["wo"]
        h = _rms_norm(x, w["mlp_norm"], eps)
        return x + (jax.nn.silu(h @ w["wg"]) * (h @ w["wu"])) @ w["wd"]


def logits(x, final_norm, lm_head, eps: float):
    """Next-token logits [n, vocab] of the rows of x: [n, d]."""
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, final_norm, eps) @ lm_head
