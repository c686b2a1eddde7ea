"""Plain reference of GLM-4.7-Flash (zai-org/GLM-4.7-Flash, ``model_type:
glm4_moe_lite``), written from the layer equations and from nothing of the
program.

Pre-norm residual layers, RMSNorm: ``h = x + Attn(norm(x))``, ``out = h +
MLP(norm(h))``, a final RMSNorm and an untied output head.

- **Attention, every layer (multi-head latent attention)**, for a token with
  normed hidden state ``x`` and ``H`` heads: ``c_q = RMSNorm(x W_dq)``;
  ``q = c_q W_uq``, per head ``[q_nope | q_rope]``; ``[c_kv | k_r] = x
  W_dkv`` (held here as two matrices, ``wdkv`` and ``wkr``); ``c_kv <-
  RMSNorm(c_kv)``; the rotary embedding on ``q_rope`` of every head and on
  the ONE ``k_r`` that all heads share; ``[k_nope | v]_h = (c_kv W_ukv)_h``;
  ``k_h = [k_nope_h | k_r]``; causal softmax of ``q_h . k_h / sqrt(d_nope +
  d_rope)``; ``o = concat_h(sum a v_h) W_o``. Keys and values are
  MATERIALISED per head here; the program serves the absorbed form over the
  latent ``[c_kv | k_r]``, which is the same function.
- **MLP**: the leading layers a dense SwiGLU; the others ``s = sigmoid(x
  W_r)`` in float32 over all routed experts; the ``k`` with the largest ``s +
  b`` (``b`` the selection bias of ``topk_method: noaux_tc``; one group, so
  no group limit); weights ``s_e / sum(s_chosen)`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; ``y = sum_e w_e SwiGLU_e(x) + SwiGLU_shared(x)``.

Departures from the published model, each of them the configuration file's
too (``assumed``): the multi-token-prediction layer (``num_nextn_predict_
layers: 1``) is a drafting layer outside the decoder stack and is left out;
the rotary embedding pairs dimension ``i`` with ``i + d_rope / 2`` (the
config says nothing of the pairing, and under seeded weights another pairing
is a permutation of ``W_uq``'s and ``W_kr``'s columns); the selection bias
is seeded and not zero, so that a bias that leaked into the weights shows.

Float32 ``jax.numpy`` with every matrix multiplication at ``highest``
precision; no cache, no absorbed form, no dispatch, no batching: one whole
sequence at a time, every expert over every token with its weight (zero
where it was not chosen), a head at a time (``o W_o`` as the sum over heads
of ``o_h W_o,h``). The heads and the experts go under ``lax.scan`` and the
blocks of query rows under ``lax.map``, not under Python loops: unrolled, a layer
took the TPU's compiler 100 s and more for every new sequence length
(PERF.md, PR 28). Weights are plain float32 arrays, matrices laid out
[in, out].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def rope_tables(n: int, rope_dim: int, theta: float):
    half = rope_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def _rope(x, cos, sin):
    """x: [T, ..., d_r]; rotates the pairs (x[..., i], x[..., i + d_r/2])."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    c, s = cos.reshape(shape), sin.reshape(shape)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _ffn(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def attention(x, w: dict, cos, sin, *, heads: int, nope: int, rope: int,
              eps: float):
    """x: [T, d] -> x + attention. ``wuq``: [r_q, H (d_n + d_r)], ``wukv``:
    [r_kv, H (d_n + d_v)], ``wo``: [H d_v, d]. One head at a time (its keys
    and values materialised from the latent, its share of ``W_o`` added to
    the sum), so that a long sequence's heads are never all live at once."""
    T = x.shape[0]
    h = _rms_norm(x, w["attn_norm"], eps)
    c_q = _rms_norm(h @ w["wdq"], w["q_norm"], eps)
    c_kv = _rms_norm(h @ w["wdkv"], w["kv_norm"], eps)
    k_r = _rope(h @ w["wkr"], cos, sin)                          # [T, d_r]
    pos = jnp.arange(T)
    block = min(QUERY_BLOCK, T)
    pad = -T % block
    at = jnp.pad(pos, (0, pad), constant_values=T - 1).reshape(-1, block)

    def head(acc, wh):
        w_uq, w_ukv, w_o = wh           # [r_q, d_q], [r_kv, d_n + d_v], [d_v, d]
        q = c_q @ w_uq
        q = jnp.concatenate(
            [q[:, :nope], _rope(q[:, nope:], cos, sin)], axis=-1)
        kv = c_kv @ w_ukv
        k = jnp.concatenate([kv[:, :nope], k_r], axis=-1)        # [T, d_q]
        v = kv[:, nope:]                                         # [T, d_v]

        def rows(args):
            qb, at_b = args                                      # [block, d_q]
            scores = (qb @ k.T) * (nope + rope) ** -0.5
            seen = pos[None, :] <= at_b[:, None]
            return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1) @ v

        qp = jnp.pad(q, ((0, pad), (0, 0))).reshape(-1, block, nope + rope)
        o = jax.lax.map(rows, (qp, at)).reshape(T + pad, -1)[:T]
        return acc + o @ w_o, None

    def by_head(m):                     # [in, H * n] -> [H, in, n]
        return m.reshape(m.shape[0], heads, -1).transpose(1, 0, 2)

    out, _ = jax.lax.scan(
        head, jnp.zeros_like(x),
        (by_head(w["wuq"]), by_head(w["wukv"]),
         w["wo"].reshape(heads, -1, x.shape[-1])))
    return x + out


def dense_ffn(x, w: dict, eps: float):
    u = _rms_norm(x, w["mlp_norm"], eps)
    return x + _ffn(u, w["wg"], w["wu"], w["wd"])


def expert_ffn(x, w: dict, *, top_k: int, scale: float, eps: float):
    """``eg``/``eu``: [E, d, f_e], ``ed``: [E, f_e, d]; ``router``: [d, E]
    and ``router_bias``: [E], both float32 as served."""
    u = _rms_norm(x, w["mlp_norm"], eps)
    s = jax.nn.sigmoid(u @ w["router"])                          # [T, E]
    _, chosen = jax.lax.top_k(s + w["router_bias"], top_k)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    gate = scale * picked / jnp.sum(picked, axis=-1, keepdims=True)
    experts = s.shape[-1]
    g = jnp.sum(jax.nn.one_hot(chosen, experts) * gate[..., None], axis=-2)

    def one(acc, e):
        a, b, c, ge = e
        return acc + ge[:, None] * _ffn(u, a, b, c), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x), (w["eg"], w["eu"], w["ed"], g.T))
    return x + routed + _ffn(u, w["sg"], w["su"], w["sd"])


def layer(x, w: dict, cos, sin, *, kind: str, heads: int, nope: int,
          rope: int, top_k: int, scale: float, eps: float):
    """One decoder layer on a whole sequence. x: [T, d] float32; ``kind``
    is ``dense`` (the leading layers) or ``experts``."""
    with jax.default_matmul_precision("highest"):
        x = attention(x, w, cos, sin, heads=heads, nope=nope, rope=rope,
                      eps=eps)
        if kind == "dense":
            return dense_ffn(x, w, eps)
        return expert_ffn(x, w, top_k=top_k, scale=scale, eps=eps)


def logits(x, final_norm, lm_head, eps: float):
    """Next-token logits [n, vocab] of the rows of x: [n, d]."""
    with jax.default_matmul_precision("highest"):
        return _rms_norm(x, final_norm, eps) @ lm_head
