"""Plain reference of Solar-Open2 (upstage/Solar-Open2-250B, ``model_type:
solar_open2``), written from the layer equations and from nothing of the
program.

Pre-norm residual layers, RMSNorm: ``x += mixer(norm(x)); x += moe(norm(x))``,
a final RMSNorm and an untied output head. Layer ``i`` is softmax
grouped-query attention where ``i % 4 == 0`` and delta-rule linear attention
with a per-channel decay (Kimi Delta Attention) otherwise.

- **Attention layer**: ``q = x Wq``, ``k = x Wk``, ``v = x Wv``, no bias, NO
  rotary embedding, causal softmax of ``q k^T / sqrt(D)``; the attention
  output is multiplied elementwise by ``sigmoid(x Wgate)`` before ``Wo``.
- **Linear-attention layer**: ``q, k, v = SiLU(conv4(x Wq | Wk | Wv))``, a
  causal depthwise convolution of width 4 over time on each stream; ``q`` and
  ``k`` L2-normalised per head, ``q`` scaled by ``D^-0.5``; log-decay ``g_t =
  -exp(A_log_h) * softplus(Wf_up(Wf_down x_t) + dt_bias)`` per head and key
  channel; ``beta_t = 2 sigmoid(x_t Wb)`` per head. The state ``S`` [heads, D,
  D] (key dim by value dim) is zero before the first token and goes token by
  token: ``S <- diag(exp(g_t)) S``; ``S <- S + beta_t k_t (v_t - S^T k_t)^T``;
  ``o_t = S^T q_t``. Output: ``Wo(RMSNorm_D(o_t) * sigmoid(Wg_up(Wg_down
  x_t)))``.
- **Expert layer**: ``s = sigmoid(x Wr)`` over all the router's experts; the
  top-k of ``s + b``; weights ``s_chosen / sum(s_chosen)`` times the scaling
  factor; SwiGLU experts, plus one always-on shared SwiGLU expert.

Float32 ``jax.numpy`` with every matrix multiplication at ``highest``
precision; no cache, no chunk form, no batching: one whole sequence at a
time, the recurrence one token at a time under ``lax.scan``, attention in
blocks of query rows so that the scores fit. The blocks of rows and the
held experts go under ``lax.map`` / ``lax.scan`` and not under Python
loops: unrolled, a layer took the TPU's compiler 100 s and more for every
new sequence length, five times what it takes so. Weights are plain float32
arrays, matrices laid out [in, out].

``held`` is the chip's share of the routed experts, ``(first, count)``: the
expert stacks hold experts ``first .. first + count - 1`` of the router's
width; what the chosen experts outside the share would add is left out, as
it is on one chip of an expert-parallel deployment. The whole layer is
``held = (0, router width)``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
L2_EPS = 1e-6        # under the square root of a head's squared norm


def _rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _attention(q, k, v):
    """Causal grouped-query attention. q: [T, H, D]; k, v: [T, K, D]."""
    T, H, D = q.shape
    K = k.shape[1]
    qg = q.reshape(T, K, H // K, D)
    pos = jnp.arange(T)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(qg, start, QUERY_BLOCK)
        rows = start + jnp.arange(QUERY_BLOCK)
        scores = jnp.einsum("tkgd,skd->kgts", qb, k) * (D ** -0.5)
        seen = pos[None, :] <= rows[:, None]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("kgts,skd->tkgd", probs, v)

    pad = -T % QUERY_BLOCK
    qg = jnp.pad(qg, ((0, pad), (0, 0), (0, 0), (0, 0)))
    out = jax.lax.map(block, jnp.arange(0, T + pad, QUERY_BLOCK))
    return out.reshape(T + pad, H * D)[:T]


def gqa_mixer(h, w: dict, *, heads: int, kv_heads: int):
    """Gated NoPE attention on the normed input h: [T, d]."""
    T = h.shape[0]
    q = (h @ w["wq"]).reshape(T, heads, -1)
    k = (h @ w["wk"]).reshape(T, kv_heads, -1)
    v = (h @ w["wv"]).reshape(T, kv_heads, -1)
    gate = jax.nn.sigmoid(h @ w["wgate"])
    return (_attention(q, k, v) * gate) @ w["wo"]


def _causal_conv(x, w):
    """Depthwise causal convolution over time. x: [T, C]; w: [width, C],
    the last row multiplying the current token."""
    width = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    T = x.shape[0]
    return sum(padded[j:j + T] * w[j] for j in range(width))


def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def linear_mixer(h, w: dict, *, heads: int, eps: float, neg_eigval: bool):
    """Delta-rule linear attention on the normed input h: [T, d]."""
    T = h.shape[0]
    qkv = jax.nn.silu(_causal_conv(
        jnp.concatenate([h @ w["lq"], h @ w["lk"], h @ w["lv"]], axis=-1),
        w["conv"]))
    kd = w["lq"].shape[1] // heads
    vd = w["lv"].shape[1] // heads
    q = qkv[:, :heads * kd].reshape(T, heads, kd)
    k = qkv[:, heads * kd:2 * heads * kd].reshape(T, heads, kd)
    v = qkv[:, 2 * heads * kd:].reshape(T, heads, vd)
    q = _l2_norm(q) * (kd ** -0.5)
    k = _l2_norm(k)
    decay = jax.nn.softplus((h @ w["f_down"]) @ w["f_up"] + w["dt_bias"])
    g = -jnp.exp(w["a_log"])[None, :, None] * decay.reshape(T, heads, kd)
    beta = jax.nn.sigmoid(h @ w["wb"]) * (2.0 if neg_eigval else 1.0)

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, :, None] * S
        read = jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + jnp.einsum("hk,hv->hkv", b_t[:, None] * k_t, v_t - read)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(
        token, jnp.zeros((heads, kd, vd), jnp.float32), (q, k, v, g, beta))
    o = _rms_norm(o, w["o_norm"], eps).reshape(T, heads * vd)
    gate = jax.nn.sigmoid((h @ w["g_down"]) @ w["g_up"])
    return (o * gate) @ w["lo"]


def experts(h, w: dict, *, top_k: int, scale: float, held: tuple):
    """The expert layer on the normed input h: [T, d]: the shared expert,
    and of the chosen routed experts those the share ``held`` holds."""
    first, count = held
    score = jax.nn.sigmoid(h @ w["router"])
    _, idx = jax.lax.top_k(score + w["router_bias"], top_k)
    chosen = jnp.take_along_axis(score, idx, axis=-1)
    weight = chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scale
    out = (jax.nn.silu(h @ w["sg"]) * (h @ w["su"])) @ w["sd"]

    def expert(out, xs):
        e, eg, eu, ed = xs
        mine = jnp.sum(jnp.where(idx == first + e, weight, 0.0), axis=-1)
        y = (jax.nn.silu(h @ eg) * (h @ eu)) @ ed
        return out + mine[:, None] * y, None

    return jax.lax.scan(
        expert, out, (jnp.arange(count), w["eg"], w["eu"], w["ed"]))[0]


def layer(x, w: dict, *, kind: str, heads: int, kv_heads: int,
          linear_heads: int, top_k: int, scale: float, eps: float,
          neg_eigval: bool, held: tuple):
    """One decoder layer of ``kind`` ("gqa" or "linear") on a whole
    sequence. x: [T, d] float32."""
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, w["attn_norm"], eps)
        if kind == "gqa":
            x = x + gqa_mixer(h, w, heads=heads, kv_heads=kv_heads)
        else:
            x = x + linear_mixer(h, w, heads=linear_heads, eps=eps,
                                 neg_eigval=neg_eigval)
        h = _rms_norm(x, w["mlp_norm"], eps)
        return x + experts(h, w, top_k=top_k, scale=scale, held=held)


def logits(x, final_norm, lm_head, eps: float):
    """Next-token logits [n, vocab] of the rows of x: [n, d]."""
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, final_norm, eps) @ lm_head
