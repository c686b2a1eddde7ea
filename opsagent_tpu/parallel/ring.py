"""Ring attention: causal self-attention with the sequence sharded over the
``sp`` mesh axis (context parallelism).

The reference has no long-context capability at all — it *shrinks* context
instead (observation truncation at 1024 tokens, reference
pkg/assistants/simple.go:495 and pkg/llms/tokens.go:128-144). Here long
sequences are first-class: each device holds S/sp of the sequence; K/V
shards rotate around the ring via ``ppermute`` (XLA lowers it onto the ICI
neighbor links) while every device accumulates flash-attention-style online
softmax statistics for its local queries. Peak memory is O(S/sp) per device
and the K/V transfer overlaps with the block attention compute — the
standard blockwise-parallel/ring formulation (PAPERS.md).

Layout contract (matching ``models.llama`` shardings):
- q: [B, S, H, D] sharded P(dp, sp, tp, None) — heads tensor-parallel
- k/v: [B, S, K, D] sharded P(dp, sp, tp, None)
- out: like q

Causality is resolved by GLOBAL position: device i's queries occupy
[i·S_l, (i+1)·S_l); at ring step s it holds the K/V block of device
(i−s) mod sp, masked with ``k_pos <= q_pos``. Whole blocks that are
entirely future still pay their block compute (simplicity over a skip
heuristic) — for the decode-vs-prefill balance this framework targets the
prefill ring is not the steady-state bottleneck.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _local_ring_attention(
    q: jax.Array,        # [B, S_l, H, D] local shard
    k: jax.Array,        # [B, S_l, K, D]
    v: jax.Array,        # [B, S_l, K, D]
    lengths: jax.Array,  # [B] valid GLOBAL lengths (right padding beyond)
    axis: str,
) -> jax.Array:
    idx = jax.lax.axis_index(axis)
    sp = jax.lax.axis_size(axis)  # static: ring step count + perm table
    B, S_l, H, D = q.shape
    K = k.shape[2]
    G = H // K
    scale = D ** -0.5
    # Operands stay in the input dtype (bf16 on TPU) — the MXU accumulates
    # in f32 via preferred_element_type; only softmax statistics are f32.
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(B, S_l, K, G, D)
    q_pos = idx * S_l + jnp.arange(S_l)                    # [S_l]

    perm = [(j, (j + 1) % sp) for j in range(sp)]

    def step(s, carry):
        k_blk, v_blk, m, l, acc = carry
        src = (idx - s) % sp
        k_pos = src * S_l + jnp.arange(S_l)                # [S_l]
        scores = jnp.einsum(
            "bskgd,btkd->bkgst", qg, k_blk,
            preferred_element_type=jnp.float32,
        )                                                   # [B,K,G,S_l,T]
        mask = (k_pos[None, :] <= q_pos[:, None])[None, None, None, :, :]
        # Ragged batches: positions past a sequence's length are padding —
        # mask them out of every ring step by GLOBAL key position, which is
        # what lets the serving prefill path shard right-padded bucketed
        # prompts over sp.
        valid = (k_pos[None, :] < lengths[:, None])[:, None, None, None, :]
        mask = jnp.logical_and(mask, valid)
        scores = jnp.where(mask, scores, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(scores - m_new)                    # [B,K,G,S_l,T]
        l_new = alpha * l + jnp.sum(probs, axis=-1, keepdims=True)
        acc_new = alpha * acc + jnp.einsum(
            "bkgst,btkd->bkgsd", probs.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        # Rotate K/V to the next device; skip on the last step (the block
        # would only be rotated home).
        k_blk, v_blk = jax.lax.cond(
            s < sp - 1,
            lambda kv: (
                jax.lax.ppermute(kv[0], axis, perm),
                jax.lax.ppermute(kv[1], axis, perm),
            ),
            lambda kv: kv,
            (k_blk, v_blk),
        )
        return k_blk, v_blk, m_new, l_new, acc_new

    # Derive the initial accumulators from q (not fresh constants) so they
    # carry q's varying-manual-axes type — shard_map's VMA tracking
    # rejects a scan whose carry starts unvarying but becomes varying.
    acc0 = jnp.moveaxis(qg, 1, 3).astype(jnp.float32) * 0.0  # [B,K,G,S_l,D]
    l0 = acc0[..., :1]
    m0 = l0 + NEG_INF
    _, _, m, l, acc = jax.lax.fori_loop(0, sp, step, (k, v, m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)                      # [B,K,G,S_l,D]
    out = jnp.moveaxis(out, 3, 1).reshape(B, S_l, H, D)
    return out.astype(q.dtype)


def make_ring_attention(
    mesh: Mesh, axis: str = "sp"
) -> Callable[..., jax.Array]:
    """Build a drop-in replacement for ``causal_prefill_attention`` that
    runs ring attention over ``axis``, for both the lengths-free training/
    oracle form and RAGGED right-padded batches (serving prefill: each
    sequence masks by its own global length inside every ring step).
    Heads stay tensor-parallel over "tp"; batch over "dp"."""
    spec = P("dp", "sp", "tp", None)
    len_spec = P("dp")  # lengths replicated over sp/tp, batch over dp
    mapped = jax.shard_map(
        functools.partial(_local_ring_attention, axis=axis),
        mesh=mesh,
        in_specs=(spec, spec, spec, len_spec), out_specs=spec,
    )

    def ring_attn(q, k, v, lengths=None):
        if lengths is None:
            lengths = jnp.full((q.shape[0],), q.shape[1], jnp.int32)
        return mapped(q, k, v, jnp.asarray(lengths, jnp.int32))

    return ring_attn
