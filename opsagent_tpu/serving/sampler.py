"""Token sampling: greedy, temperature, top-k, top-p — one fused jittable
function over the decode batch, with an optional constrained-decoding mask.

The near-greedy default mirrors the reference client's
``Temperature: math.SmallestNonzeroFloat32`` (reference pkg/llms/openai.go:73):
temperature 0 means argmax.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..utils.profiling import scoped

NEG_INF = -1e30


@dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.0
    top_k: int = 0          # 0 = disabled
    top_p: float = 1.0      # 1.0 = disabled
    max_tokens: int = 2048
    stop: tuple[str, ...] = ()
    # OpenAI logprobs: False = off; True returns each sampled token's
    # logprob, with top_logprobs (0..20) alternatives per position.
    logprobs: bool = False
    top_logprobs: int = 0
    # OpenAI logit_bias ({token_id: -100..100}, stored as pairs for
    # hashability) and repetition penalties (-2..2): together they form
    # one additive per-token bias applied to logits before sampling.
    logit_bias: tuple[tuple[int, float], ...] = ()
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    # Tokens generated before an engine restart (set by the scheduler's
    # recovery path): penalty counting includes them, so sampling behavior
    # does not silently change because a slice restarted mid-request.
    penalty_history: tuple[int, ...] = ()


# Candidate-set size for top-k / top-p sampling. Full-vocab SORTS are the
# dominant cost of a fused decode+sample step on TPU (a [B, 128k] sort
# dwarfs the decode matmuls at small batch), so truncation-based sampling
# works on the top-MAX_CANDIDATES logits from one cheap ``lax.top_k``.
# Plain temperature sampling does NOT go through the candidate set — it is
# computed exactly over the full vocab with the Gumbel-argmax trick (argmax
# of logits/t + Gumbel noise ~ categorical(softmax(logits/t))), which needs
# no sort at all. Only requests that themselves ask for truncation
# (top_k > 0, clamped to 64, or top_p < 1) use the candidate list.
MAX_CANDIDATES = 64


@scoped("sample")
def sample(
    logits: jax.Array,             # [B, V] float32
    key: jax.Array,
    temperature: jax.Array,        # [B]
    top_k: jax.Array,              # [B] int32 (0 = off)
    top_p: jax.Array,              # [B] float32 (1.0 = off)
    allowed_mask: jax.Array | None = None,  # [B, V] bool; False = forbidden
) -> jax.Array:
    """Sample one token per row, sort-free. Per row:

    - temperature <= 0: argmax (the agent-loop default).
    - temperature > 0, no top-k/top-p: EXACT full-vocab categorical via
      Gumbel-argmax.
    - top_k > 0 and/or top_p < 1: truncated sampling over the descending
      top-``MAX_CANDIDATES`` candidate list (top_k clamped to it; top-p
      mass computed within it), mapped back to vocab ids."""
    B, V = logits.shape
    if allowed_mask is not None:
        logits = jnp.where(allowed_mask, logits, NEG_INF)

    t = jnp.maximum(temperature, 1e-6)[:, None]
    # Independent keys: the full-vocab Gumbel draw and categorical's
    # internal draw must not share Threefry counter space.
    k_noisy, k_trunc = jax.random.split(key)

    # -- exact paths: greedy and Gumbel-argmax temperature sampling.
    gumbel = jax.random.gumbel(k_noisy, (B, V), dtype=logits.dtype)
    noisy = jnp.argmax(logits / t + gumbel, axis=-1)
    greedy = jnp.argmax(logits, axis=-1)

    # -- truncated path over the candidate list.
    C = min(MAX_CANDIDATES, V)
    vals, idx = jax.lax.top_k(logits, C)           # [B, C] descending
    kk = jnp.where(top_k > 0, jnp.minimum(top_k, C), C)      # [B]
    pos = jnp.arange(C)[None, :]
    scaled = jnp.where(pos < kk[:, None], vals, NEG_INF) / t
    # top-p: keep the smallest prefix reaching top_p mass (always >= 1).
    probs = jax.nn.softmax(scaled, axis=-1)
    cumsum = jnp.cumsum(probs, axis=-1)
    keep = cumsum - probs < top_p[:, None]
    scaled_p = jnp.where(keep, scaled, NEG_INF)
    choice = jax.random.categorical(k_trunc, scaled_p, axis=-1)  # [B] in [0, C)
    truncated = jnp.take_along_axis(idx, choice[:, None], axis=1)[:, 0]

    wants_truncation = (top_k > 0) | (top_p < 1.0)
    sampled = jnp.where(wants_truncation, truncated, noisy)
    return jnp.where(temperature <= 0.0, greedy, sampled)
