"""Plain reference of a throw-away decoder family: latent attention with
routed and shared experts. It stands for no model; it exists so that a test
can add a second family to the harness from new files only.

Written from the equations, in straightforward ``jax.numpy`` float32 with
every matrix multiplication at ``highest`` precision, one whole sequence at
a time. It imports nothing of the program and takes its weights as plain
float32 arrays, matrices laid out [in, out], in their published shapes.

Attention (latent, with a low-rank query and a decoupled rotary part), for
a token t with normed hidden state h_t and H heads:

    c^Q_t = norm(h_t W^DQ)                 the query's latent        [r_q]
    [q^C_t,i ; q^R_t,i] = (c^Q_t W^UQ)_i   per head: content, rotary [d_n ; d_r]
    c^KV_t = norm(h_t W^DKV)               the latent the cache holds [r_kv]
    k^R_t = rope(h_t W^KR)                 one rotary key for all heads [d_r]
    [k^C_t,i ; v_t,i] = (c^KV_t W^UKV)_i   per head: content key, value [d_n ; d_v]
    score_i(t, s) = (q^C_t,i . k^C_s,i + rope(q^R_t,i) . k^R_s) / sqrt(d_n + d_r)
    o_t,i = sum_{s <= t} softmax_s(score_i(t, s)) v_s,i
    x_t <- x_t + [o_t,1 .. o_t,H] W^O

Experts, for a token with normed hidden state u, E routed experts, top k:

    s = sigmoid(u W^router)                                         [E]
    chosen = the k experts with the largest s + b   (b steers the choice only)
    g_e = scale * s_e / sum_{e' chosen} s_e'        for e chosen, else 0
    x <- x + sum_e g_e FFN_e(u) + FFN_shared(u),   FFN(u) = (silu(u W^g) * (u W^u)) W^d
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
    [r_kv, H (d_n + d_v)], ``wo``: [H d_v, d]."""
    T = x.shape[0]
    h = _rms_norm(x, w["attn_norm"], eps)
    q = (_rms_norm(h @ w["wdq"], w["q_norm"], eps) @ w["wuq"])
    q = q.reshape(T, heads, nope + rope)
    q_c, q_r = q[..., :nope], _rope(q[..., nope:], cos, sin)
    latent = _rms_norm(h @ w["wdkv"], w["kv_norm"], eps)
    k_r = _rope(h @ w["wkr"], cos, sin)                          # [T, d_r]
    kv = (latent @ w["wukv"]).reshape(T, heads, -1)
    k_c, v = kv[..., :nope], kv[..., nope:]
    pos = jnp.arange(T)
    out = []
    for start in range(0, T, QUERY_BLOCK):
        rows = slice(start, start + QUERY_BLOCK)
        scores = (jnp.einsum("thd,shd->hts", q_c[rows], k_c)
                  + jnp.einsum("thd,sd->hts", q_r[rows], k_r))
        scores = scores * (nope + rope) ** -0.5
        seen = pos[None, :] <= pos[rows, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        out.append(jnp.einsum("hts,shd->thd", probs, v))
    o = jnp.concatenate(out, axis=0).reshape(T, -1)
    return x + o @ w["wo"]


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
    each = jax.vmap(lambda a, b, c: _ffn(u, a, b, c))(
        w["eg"], w["eu"], w["ed"])                               # [E, T, d]
    routed = jnp.einsum("te,etd->td", g, each)
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
