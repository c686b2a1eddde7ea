"""Plain reference of Jamba's dense models (ai21labs/AI21-Jamba2-3B,
``model_type: jamba``, ``num_experts: 1``), written from the layer equations
and from nothing of the program.

Pre-norm residual layers, RMSNorm: ``x = x + mixer(norm(x)); x = x +
mlp(norm(x))``, a final RMSNorm and an output head (whatever head it is
handed: the published model ties it to the embedding, and a caller that
wants that passes the embedding's transpose). The MLP is SwiGLU. Layer ``i``
is attention where ``i % attn_layer_period == attn_layer_offset`` and a
Mamba-1 mixer otherwise; no positional embedding anywhere.

- **Mamba layer**, for one sequence, ``u`` the normed input:
  1. ``[x, z] = u W_in`` (``x`` first, ``z`` second), no bias.
  2. ``x = SiLU(conv4(x) + b_conv)``: a causal depthwise convolution of
     width 4 over time.
  3. ``[dt_low, B, C] = x W_x`` (no bias), then Jamba's own addition: each of
     the three through an RMSNorm with a learned weight (eps
     ``rms_norm_eps``).
  4. ``dt = softplus(dt_low W_dt + b_dt)``; ``A = -exp(A_log)``.
  5. ``h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] B_t[n]
     x_t[c]``, ``h_0 = 0``; ``y_t[c] = sum_n C_t[n] h_t[c, n] + D[c] x_t[c]``.
  6. ``out = (y * SiLU(z)) W_out``.
- **Attention layer**: ``q = u Wq`` over ``heads`` heads, ``k = u Wk`` and
  ``v = u Wv`` over ``kv_heads`` (one in the 3B: multi-query), no bias, no
  rotary embedding; causal softmax of ``q k^T / sqrt(D)``.

Departures from the published description: none in the mathematics.
``A_log`` is taken ``[d_state, d_inner]``, the transpose of a checkpoint's
``[d_inner, d_state]`` (the same numbers; the state is written ``h[c, n]``
here as published). What the config has no key for is the model type's own
rule in ``transformers`` and is listed in the configuration's file under
``assumed``: which layers attend, and a head dim of ``hidden_size /
num_attention_heads``.

Float32 ``jax.numpy`` with every matrix multiplication at ``highest``
precision; no cache, no kernels, no batching: one whole sequence at a time,
the recurrence one token at a time under ``lax.scan``, attention in blocks
of query rows under ``lax.map`` so that the scores fit and a new sequence
length compiles one block, not many. Weights are plain float32 arrays,
matrices laid out [in, out].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def _rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _attention(q, k, v):
    """Causal grouped-query attention. q: [T, H, D]; k, v: [T, K, D], head
    ``h`` reading kv head ``h // (H / K)``."""
    T, H, D = q.shape
    K = k.shape[1]
    pos = jnp.arange(T)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, QUERY_BLOCK)
        qb = qb.reshape(QUERY_BLOCK, K, H // K, D)
        rows = start + jnp.arange(QUERY_BLOCK)
        scores = jnp.einsum("tkgd,skd->kgts", qb, k) * (D ** -0.5)
        seen = pos[None, :] <= rows[:, None]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        out = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(scores, axis=-1), v)
        return out.reshape(QUERY_BLOCK, H * D)

    pad = -T % QUERY_BLOCK
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    out = jax.lax.map(block, jnp.arange(0, T + pad, QUERY_BLOCK))
    return out.reshape(T + pad, H * D)[:T]


def attention_mixer(u, w: dict, *, heads: int, kv_heads: int):
    """NoPE grouped-query attention on the normed input u: [T, d]."""
    T = u.shape[0]
    q = (u @ w["wq"]).reshape(T, heads, -1)
    k = (u @ w["wk"]).reshape(T, kv_heads, -1)
    v = (u @ w["wv"]).reshape(T, kv_heads, -1)
    return _attention(q, k, v) @ w["wo"]


def _causal_conv(x, w):
    """Depthwise causal convolution over time. x: [T, C]; w: [width, C],
    the last row multiplying the current token."""
    width = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    T = x.shape[0]
    return sum(padded[j:j + T] * w[j] for j in range(width))


def mamba_mixer(u, w: dict, *, eps: float):
    """Mamba-1 with Jamba's dt/B/C norms on the normed input u: [T, d]."""
    di = w["m_out"].shape[0]
    ds = w["b_norm"].shape[0]
    r = w["dt_norm"].shape[0]
    xz = u @ w["m_in"]                                          # 1
    x, z = xz[:, :di], xz[:, di:]
    x = jax.nn.silu(_causal_conv(x, w["conv"]) + w["conv_b"])   # 2
    low = x @ w["m_x"]                                          # 3
    dt_low = _rms_norm(low[:, :r], w["dt_norm"], eps)
    B = _rms_norm(low[:, r:r + ds], w["b_norm"], eps)
    C = _rms_norm(low[:, r + ds:], w["c_norm"], eps)
    dt = jax.nn.softplus(dt_low @ w["m_dt"] + w["dt_bias"])     # 4
    A = -jnp.exp(w["a_log"]).T                                  # [di, ds]

    def token(h, xs):                                           # 5
        x_t, dt_t, b_t, c_t = xs
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * x_t)[:, None] * b_t[None]
        return h, jnp.sum(h * c_t[None], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((di, ds), x.dtype), (x, dt, B, C))
    y = y + w["d_skip"] * x
    return (y * jax.nn.silu(z)) @ w["m_out"]                    # 6


def layer(x, w: dict, *, kind: str, heads: int, kv_heads: int, eps: float):
    """One decoder layer of ``kind`` ("mamba" or "attention") on a whole
    sequence. x: [T, d] float32."""
    with jax.default_matmul_precision("highest"):
        u = _rms_norm(x, w["attn_norm"], eps)
        if kind == "attention":
            x = x + attention_mixer(u, w, heads=heads, kv_heads=kv_heads)
        else:
            x = x + mamba_mixer(u, w, eps=eps)
        u = _rms_norm(x, w["mlp_norm"], eps)
        return x + (jax.nn.silu(u @ w["wg"]) * (u @ w["wu"])) @ w["wd"]


def logits(x, final_norm, lm_head, eps: float):
    """Next-token logits [n, vocab] of the rows of x: [n, d]."""
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, final_norm, eps) @ lm_head
