"""Delta-rule linear attention with a per-channel decay (Kimi Delta
Attention), in plain ``jax.numpy`` under XLA.

Per head, with a float32 state ``S`` [key dim, value dim]:

    S <- diag(exp(g_t)) S
    S <- S + beta_t k_t (v_t - S^T k_t)^T
    o_t = S^T q_t

Two forms over the same mathematics:

- ``delta_rule_step``: one token for each row, the recurrence as written
  (decode lanes, the fused decode block).
- ``delta_rule_chunk``: ``n > 1`` tokens of each row from the row's stored
  state, in blocks of ``BLOCK`` tokens. Inside a block the update is
  rewritten over pseudo-values ``u_i = beta_i (v_i - (decayed S_{i-1})^T
  k_i)``, which solve a unit lower-triangular system ``(I + diag(beta) A) u
  = beta (v - K~ S_0)`` with ``A_ij = k_i . (exp(G_i - G_j) k_j)``, ``G`` the
  running sum of ``g`` inside the block. The decays ``exp(G_i - G_j)`` are
  never split into ``exp(G_i) exp(-G_j)`` across a whole block (the second
  overflows under a strong decay): across sub-blocks of ``SUB`` tokens they
  are split at the later sub-block's start, where both factors are at most
  one, and inside a sub-block they are taken directly. The system is solved
  by forward substitution in sub-blocks. A scan over the blocks carries the
  state.

Rows are ragged: positions at or past a row's ``valid`` count get ``g = 0``
and ``beta = 0``, which leave the state as it was. Everything here is
float32 and every matrix product is at ``highest`` precision: the state
is the sequence's memory, and an error in it never decays away.

``conv_with_tail`` is the causal depthwise convolution in front of the
mixer, continued from the last inputs of the row's previous tokens.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK = 64
SUB = 16
_HI = jax.lax.Precision.HIGHEST


def conv_with_tail(x, tail, w, valid):
    """Causal depthwise convolution over time, continued from ``tail``.

    x: [B, S, C] new inputs; tail: [B, W-1, C] the row's last inputs before
    them (zeros at a sequence's start); w: [W, C], the last row multiplying
    the current token; valid: [B] how many of the S positions are real.
    Returns (y [B, S, C], new tail [B, W-1, C]: the last W-1 inputs up to
    and including each row's last valid position)."""
    width = w.shape[0]
    S = x.shape[1]
    xs = jnp.concatenate([tail.astype(x.dtype), x], axis=1)   # [B, S+W-1, C]
    y = sum(xs[:, j:j + S] * w[j] for j in range(width))
    at = valid[:, None] + jnp.arange(width - 1)[None, :]       # [B, W-1]
    new_tail = jnp.take_along_axis(xs, at[:, :, None], axis=1)
    return y, new_tail


def delta_rule_step(q, k, v, g, beta, state):
    """One token a row. q, k, g: [B, H, dk]; v: [B, H, dv]; beta: [B, H];
    state: [B, H, dk, dv]. Returns (o [B, H, dv], new state). Elementwise
    float32 throughout: nothing here goes through the matrix unit."""
    state = state * jnp.exp(g)[..., None]
    read = jnp.sum(state * k[..., None], axis=-2)              # S^T k
    state = state + (beta[..., None] * k)[..., None] * (v - read)[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def _unit_lower_inverse(n_mat):
    """(I + N)^-1 for strictly lower-triangular N [..., m, m], by forward
    substitution a row at a time (m is small: a sub-block)."""
    m = n_mat.shape[-1]
    inv = jnp.broadcast_to(jnp.eye(m, dtype=n_mat.dtype), n_mat.shape)
    for i in range(1, m):
        row = -jnp.sum(n_mat[..., i, :, None] * inv, axis=-2)
        inv = inv.at[..., i, :].set(row + inv[..., i, :])
    return inv


def _block_terms(q, k, v, g, beta):
    """What a block needs that does not depend on the incoming state.

    q, k, g: [..., C, dk]; v: [..., C, dv]; beta: [..., C] (leading axes:
    batch, block, head). Returns (W [..., C, dk], U [..., C, dv], Bq [..., C,
    C], q~ [..., C, dk], k^ [..., C, dk], decay of the whole block [..., dk]):
    ``u = U - W S_0``, ``o = q~ S_0 + Bq u``, ``S_C = decay S_0 + k^T u``."""
    C, dk = k.shape[-2:]
    sub = SUB if C % SUB == 0 else C
    n = C // sub
    G = jnp.cumsum(g, axis=-2)                                 # inclusive
    lead = G.shape[:-2]
    Gs = G.reshape(*lead, n, sub, dk)
    ks = k.reshape(*lead, n, sub, dk)
    qs = q.reshape(*lead, n, sub, dk)
    # r_a: the running sum just before sub-block a's first token
    r = jnp.concatenate(
        [jnp.zeros_like(Gs[..., :1, 0, :]), Gs[..., :-1, -1, :]], axis=-2
    )                                                          # [..., n, dk]
    k_in = ks * jnp.exp(Gs - r[..., :, None, :])               # <= 1
    q_in = qs * jnp.exp(Gs - r[..., :, None, :])
    # earlier sub-block b seen from sub-block a: exp(r_a - G_j) <= 1
    earlier = (jnp.arange(n)[:, None] > jnp.arange(n)[None, :])
    expo = r[..., :, None, None, :] - Gs[..., None, :, :, :]   # [...,a,b,j,dk]
    k_out = ks[..., None, :, :, :] * jnp.exp(
        jnp.where(earlier[:, :, None, None], expo, -jnp.inf))
    A = jnp.einsum("...aid,...abjd->...aibj", k_in, k_out, precision=_HI)
    Bq = jnp.einsum("...aid,...abjd->...aibj", q_in, k_out, precision=_HI)
    # inside a sub-block: the decays taken directly
    i = jnp.arange(sub)
    diff = Gs[..., :, :, None, :] - Gs[..., :, None, :, :]     # [...,a,i,j,dk]
    E = jnp.exp(jnp.where((i[:, None] >= i[None, :])[:, :, None], diff,
                          -jnp.inf))
    kk = ks[..., :, :, None, :] * ks[..., :, None, :, :] * E
    qk = qs[..., :, :, None, :] * ks[..., :, None, :, :] * E
    A_in = jnp.sum(kk, axis=-1) * (i[:, None] > i[None, :])    # strict
    Bq_in = jnp.sum(qk, axis=-1)
    same = jnp.eye(n, dtype=bool)[:, None, :, None]
    A = A + jnp.where(same, A_in[..., :, :, None, :], 0.0)
    Bq = Bq + jnp.where(same, Bq_in[..., :, :, None, :], 0.0)
    # (I + diag(beta) A) [W | U] = beta [k~ | v], by sub-blocks
    bs = beta.reshape(*lead, n, sub)
    M = bs[..., :, :, None, None] * A                          # [...,a,i,b,j]
    inv = _unit_lower_inverse(
        jnp.stack([M[..., a, :, a, :] for a in range(n)], axis=-3))
    k_fwd = k * jnp.exp(G)                                     # k~: <= 1
    rhs = (beta[..., None] * jnp.concatenate([k_fwd, v], axis=-1)).reshape(
        *lead, n, sub, -1)
    solved = []
    for a in range(n):
        acc = rhs[..., a, :, :]
        for b in range(a):
            acc = acc - jnp.einsum(
                "...ij,...jd->...id", M[..., a, :, b, :], solved[b],
                precision=_HI)
        solved.append(jnp.einsum(
            "...ij,...jd->...id", inv[..., a, :, :], acc, precision=_HI))
    WU = jnp.concatenate(solved, axis=-2)                      # [..., C, dk+dv]
    total = G[..., -1, :]
    k_end = k * jnp.exp(total[..., None, :] - G)               # k^: <= 1
    return (WU[..., :dk], WU[..., dk:], Bq.reshape(*lead, C, C),
            q * jnp.exp(G), k_end, jnp.exp(total))


def delta_rule_chunk(q, k, v, g, beta, state, valid):
    """``S`` tokens a row from the row's state. q, k, g: [B, S, H, dk]; v:
    [B, S, H, dv]; beta: [B, S, H]; state: [B, H, dk, dv]; valid: [B] real
    positions of each row (the rest leave the state untouched). Returns
    (o [B, S, H, dv], new state)."""
    B, S, H, dk = k.shape
    real = (jnp.arange(S)[None, :] < valid[:, None])
    g = jnp.where(real[:, :, None, None], g, 0.0)
    beta = jnp.where(real[:, :, None], beta, 0.0)
    C = BLOCK if S > BLOCK else (S if S <= SUB else -(-S // SUB) * SUB)
    pad = -S % C
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    nb = (S + pad) // C

    def blocks(a):          # [B, S, H, x] -> [B, nb, H, C, x]
        return a.reshape(B, nb, C, H, -1).transpose(0, 1, 3, 2, 4)

    W, U, Bq, q_fwd, k_end, decay = _block_terms(
        blocks(q), blocks(k), blocks(v), blocks(g),
        blocks(beta[..., None])[..., 0])

    def block(S0, xs):
        W_, U_, Bq_, q_, k_, d_ = xs
        u = U_ - jnp.einsum("bhck,bhkv->bhcv", W_, S0, precision=_HI)
        o = (jnp.einsum("bhck,bhkv->bhcv", q_, S0, precision=_HI)
             + jnp.einsum("bhcj,bhjv->bhcv", Bq_, u, precision=_HI))
        S1 = d_[..., None] * S0 + jnp.einsum(
            "bhck,bhcv->bhkv", k_, u, precision=_HI)
        return S1, o

    state, o = jax.lax.scan(
        block, state,
        tuple(jnp.moveaxis(a, 1, 0) for a in (W, U, Bq, q_fwd, k_end, decay)))
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4).reshape(B, nb * C, H, -1)
    return o[:, :S], state
