"""Gated delta-rule linear attention in plain ``jax.numpy`` under XLA: the
mixer of Kimi Delta Attention (a decay for every key channel) and of Gated
DeltaNet (one decay a head).

Per head, with a float32 state ``S`` [key dim, value dim] (the two dims
need not be equal):

    S <- diag(exp(g_t)) S
    S <- S + beta_t k_t (v_t - S^T k_t)^T
    o_t = S^T q_t

``g_t`` is ``[.., H, dk]`` (a decay a channel) or ``[.., H]`` (a decay a
head: ``diag(exp(g_t))`` is then ``exp(g_t) I``). Which one is read from
the shape of ``g``; nothing else tells the two apart.

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
  overflows under a strong decay). With a decay a channel, across
  sub-blocks of ``SUB`` tokens they are split at the later sub-block's
  start, where both factors are at most one, and inside a sub-block they
  are taken directly (``[SUB, SUB, dk]`` elementwise sums). With a decay a
  head they are one ``[C, C]`` matrix ``Gamma`` a head, taken directly, and
  ``A = (K K^T) * Gamma`` and ``Bq = (Q K^T) * Gamma`` go through the matrix
  unit. Either way every decay factor is at most one. The system is solved
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
    """One token a row. q, k: [B, H, dk]; g: [B, H, dk] or [B, H]; v: [B, H,
    dv]; beta: [B, H]; state: [B, H, dk, dv]. Returns (o [B, H, dv], new
    state). Elementwise float32 throughout: nothing here goes through the
    matrix unit."""
    decay = jnp.exp(g)
    state = state * (decay[..., None] if g.ndim == k.ndim
                     else decay[..., None, None])
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


def _pairs_by_channel(q, k, G, n: int, sub: int):
    """``A_ij = k_i . (exp(G_i - G_j) k_j)`` (strictly lower) and ``Bq_ij =
    q_i . (exp(G_i - G_j) k_j)`` (lower), [..., n, sub, n, sub], with a
    decay a channel. q, k, G: [..., C, dk]."""
    dk = k.shape[-1]
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
    return A, Bq


def _pairs_by_head(q, k, G, n: int, sub: int):
    """The same two with a decay a head, G: [..., C]: ``Gamma_ij = exp(G_i -
    G_j)`` for ``i >= j`` is one ``[C, C]`` matrix a head, taken directly
    (``G`` never rises, so every entry is at most one), and multiplies
    ``K K^T`` and ``Q K^T`` from the matrix unit."""
    lead, C = G.shape[:-1], G.shape[-1]
    i = jnp.arange(C)
    gamma = jnp.exp(jnp.where(
        i[:, None] >= i[None, :], G[..., :, None] - G[..., None, :], -jnp.inf))
    A = jnp.einsum("...id,...jd->...ij", k, k, precision=_HI) * gamma
    Bq = jnp.einsum("...id,...jd->...ij", q, k, precision=_HI) * gamma
    A = A * (i[:, None] > i[None, :])                          # strict
    return (A.reshape(*lead, n, sub, n, sub),
            Bq.reshape(*lead, n, sub, n, sub))


def _block_terms(q, k, v, g, beta):
    """What a block needs that does not depend on the incoming state.

    q, k: [..., C, dk]; g: [..., C, dk] or [..., C]; v: [..., C, dv]; beta:
    [..., C] (leading axes: batch, block, head). Returns (W [..., C, dk], U
    [..., C, dv], Bq [..., C, C], q~ [..., C, dk], k^ [..., C, dk], decay of
    the whole block [..., dk] or [..., 1]): ``u = U - W S_0``, ``o = q~ S_0
    + Bq u``, ``S_C = decay S_0 + k^T u``."""
    C, dk = k.shape[-2:]
    sub = SUB if C % SUB == 0 else C
    n = C // sub
    lead = k.shape[:-2]
    if g.ndim == k.ndim:
        G = jnp.cumsum(g, axis=-2)                             # inclusive
        A, Bq = _pairs_by_channel(q, k, G, n, sub)
    else:
        G = jnp.cumsum(g, axis=-1)
        A, Bq = _pairs_by_head(q, k, G, n, sub)
        G = G[..., None]                    # one decay for every channel
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
    """``S`` tokens a row from the row's state. q, k: [B, S, H, dk]; g: [B,
    S, H, dk] or [B, S, H]; v: [B, S, H, dv]; beta: [B, S, H]; state: [B, H,
    dk, dv]; valid: [B] real positions of each row (the rest leave the state
    untouched). Returns (o [B, S, H, dv], new state)."""
    B, S, H, dk = k.shape
    real = (jnp.arange(S)[None, :] < valid[:, None])
    g = jnp.where(real.reshape(B, S, *([1] * (g.ndim - 2))), g, 0.0)
    beta = jnp.where(real[:, :, None], beta, 0.0)
    C = BLOCK if S > BLOCK else (S if S <= SUB else -(-S // SUB) * SUB)
    pad = -S % C
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    nb = (S + pad) // C

    def blocks(a):          # [B, S, H, x] -> [B, nb, H, C, x]
        return a.reshape(B, nb, C, H, -1).transpose(0, 1, 3, 2, 4)

    def blocks_of_scalars(a):   # [B, S, H] -> [B, nb, H, C]
        return blocks(a[..., None])[..., 0]

    W, U, Bq, q_fwd, k_end, decay = _block_terms(
        blocks(q), blocks(k), blocks(v),
        blocks(g) if g.ndim == k.ndim else blocks_of_scalars(g),
        blocks_of_scalars(beta))

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
