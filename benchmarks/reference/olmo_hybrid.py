"""Plain reference of Olmo-Hybrid (allenai/Olmo-Hybrid-7B, ``model_type:
olmo_hybrid``), written from the layer equations and from nothing of the
program.

Post-norm residual layers, RMSNorm (the Olmo2 block): ``h = x +
norm(mixer(x)); out = h + norm(mlp(h))``, nothing normed BEFORE a sublayer, a
final RMSNorm and an untied output head. The MLP is SwiGLU. Layer ``i`` is
gated delta-rule linear attention where ``layer_types[i]`` says
``linear_attention`` (three of every four) and full softmax attention where it
says ``full_attention`` (the last of every four).

- **Linear layer** (FLA's Gated DeltaNet, which the config's ``linear_*`` keys
  and ratios are): ``q, k, v = SiLU(conv4(x Wq | Wk | Wv))``, a causal
  depthwise convolution of width 4 over time on each stream, no bias; ``q``
  and ``k`` L2-normalised per head, ``q`` scaled by ``dk^-0.5``; ``beta_t = 2
  sigmoid(x_t Wb)`` per head (the 2 is ``linear_allow_neg_eigval``); log-decay
  ``g_t = -exp(A_log_h) * softplus(x_t Wa + dt_bias_h)``: ONE number a head.
  The state ``S`` [heads, dk, dv] (96 by 192 in the 7B) is zero before the
  first token and goes token by token: ``S <- exp(g_t) S``; ``S <- S + beta_t
  k_t (v_t - S^T k_t)^T``; ``o_t = S^T q_t``. Output: ``(RMSNorm_dv(o_t) *
  SiLU(x_t Wg)) Wo`` with ``Wg`` full rank.
- **Full-attention layer**: ``q = x Wq``, ``k = x Wk``, ``v = x Wv``, no bias;
  RMSNorm over the WHOLE width of ``q`` and of ``k`` before the heads are
  split; no rotary embedding; causal softmax of ``q k^T / sqrt(D)``; as many
  kv heads as heads.

Departures from the published layer, each because the config has no key for
it (the configuration's file lists the same under ``assumed``):

- the block's order and the whole-width q/k norm are Olmo2's and Olmo3's
  (``transformers``; the same organisation's earlier ``model_type``s), not
  read from this model's own modelling code, which is not available here;
- no positional embedding at all: ``rope_parameters.rope_theta`` is null;
- the output gate's SiLU, the per-head RMSNorm of the read-out, one ``A_log``
  and one ``dt_bias`` a head, the L2 norm's epsilon of 1e-6 and the absence
  of a conv bias are FLA's ``GatedDeltaNet`` layer as published; the norms'
  epsilon is the config's ``rms_norm_eps`` throughout.

Float32 ``jax.numpy`` with every matrix multiplication at ``highest``
precision; no cache, no chunk form, no batching: one whole sequence at a
time, the recurrence one token at a time under ``lax.scan``, attention in
blocks of query rows under ``lax.map`` so that the scores fit and a new
sequence length compiles one block, not many. Weights are plain float32
arrays, matrices laid out [in, out].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
L2_EPS = 1e-6        # under the square root of a head's squared norm


def _rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _attention(q, k, v):
    """Causal attention, a kv head for every head. q, k, v: [T, H, D]."""
    T, H, D = q.shape
    pos = jnp.arange(T)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, QUERY_BLOCK)
        rows = start + jnp.arange(QUERY_BLOCK)
        scores = jnp.einsum("thd,shd->hts", qb, k) * (D ** -0.5)
        seen = pos[None, :] <= rows[:, None]
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)

    pad = -T % QUERY_BLOCK
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    out = jax.lax.map(block, jnp.arange(0, T + pad, QUERY_BLOCK))
    return out.reshape(T + pad, H * D)[:T]


def full_mixer(x, w: dict, *, heads: int, eps: float):
    """NoPE attention with whole-width q/k norms on the layer's input x:
    [T, d] (not normed: the block norms the sublayer's output)."""
    T = x.shape[0]
    q = _rms_norm(x @ w["wq"], w["qn"], eps).reshape(T, heads, -1)
    k = _rms_norm(x @ w["wk"], w["kn"], eps).reshape(T, heads, -1)
    v = (x @ w["wv"]).reshape(T, heads, -1)
    return _attention(q, k, v) @ w["wo"]


def _causal_conv(x, w):
    """Depthwise causal convolution over time. x: [T, C]; w: [width, C],
    the last row multiplying the current token."""
    width = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    T = x.shape[0]
    return sum(padded[j:j + T] * w[j] for j in range(width))


def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def linear_mixer(x, w: dict, *, heads: int, eps: float, neg_eigval: bool):
    """Gated delta-rule linear attention on the layer's input x: [T, d]."""
    T = x.shape[0]
    qkv = jax.nn.silu(_causal_conv(
        jnp.concatenate([x @ w["lq"], x @ w["lk"], x @ w["lv"]], axis=-1),
        w["conv"]))
    kd = w["lq"].shape[1] // heads
    vd = w["lv"].shape[1] // heads
    q = qkv[:, :heads * kd].reshape(T, heads, kd)
    k = qkv[:, heads * kd:2 * heads * kd].reshape(T, heads, kd)
    v = qkv[:, 2 * heads * kd:].reshape(T, heads, vd)
    q = _l2_norm(q) * (kd ** -0.5)
    k = _l2_norm(k)
    g = -jnp.exp(w["a_log"]) * jax.nn.softplus(x @ w["wa"] + w["dt_bias"])
    beta = jax.nn.sigmoid(x @ w["wb"]) * (2.0 if neg_eigval else 1.0)

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs                 # g_t, b_t: [heads]
        S = jnp.exp(g_t)[:, None, None] * S
        read = jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + jnp.einsum("hk,hv->hkv", b_t[:, None] * k_t, v_t - read)
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(
        token, jnp.zeros((heads, kd, vd), q.dtype), (q, k, v, g, beta))
    o = _rms_norm(o, w["o_norm"], eps).reshape(T, heads * vd)
    return (o * jax.nn.silu(x @ w["wog"])) @ w["lo"]


def layer(x, w: dict, *, kind: str, heads: int, linear_heads: int,
          eps: float, neg_eigval: bool):
    """One decoder layer of ``kind`` ("full" or "linear") on a whole
    sequence. x: [T, d] float32."""
    with jax.default_matmul_precision("highest"):
        if kind == "full":
            y = full_mixer(x, w, heads=heads, eps=eps)
        else:
            y = linear_mixer(x, w, heads=linear_heads, eps=eps,
                             neg_eigval=neg_eigval)
        x = x + _rms_norm(y, w["attn_norm"], eps)
        y = (jax.nn.silu(x @ w["wg"]) * (x @ w["wu"])) @ w["wd"]
        return x + _rms_norm(y, w["mlp_norm"], eps)


def logits(x, final_norm, lm_head, eps: float):
    """Next-token logits [n, vocab] of the rows of x: [n, d]."""
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, final_norm, eps) @ lm_head
