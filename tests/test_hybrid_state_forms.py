"""The delta rule's forms against each other, with no model around them
(tests/hybrid_state_common.py has the tolerances): the chunk form is the
token recurrence, with a decay a channel and with a decay a head; a decay a
head is the channel form with ``g`` broadcast; and a slot's state is held
lane-dense.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opsagent_tpu.models import llama
from opsagent_tpu.ops.linear_attention import delta_rule_chunk, delta_rule_step
from hybrid_state_common import (  # noqa: F401 (fixtures)
    OLMO,
    OLMO_TOL,
    PAGE,
    highest,
    release_compiled_programs,
    table_rows,
)


@pytest.mark.parametrize("S,strong,valid", [
    (5, 0, None), (16, 1, None), (48, 1, None), (64, 0, [1, 17, 64]),
    (128, 1, [0, 70, 128]), (200, 0, None),
])
def test_the_chunk_form_is_the_token_recurrence(S, strong, valid):
    """Across block and sub-block boundaries, under decays strong enough
    to overflow a split ``exp(G_i) exp(-G_j)``, and on ragged rows."""
    B, H, dk, dv = 3, 2, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(S + strong), 6)
    q = jax.random.normal(ks[0], (B, S, H, dk))
    k = jax.random.normal(ks[1], (B, S, H, dk))
    q, k = (a / jnp.linalg.norm(a, axis=-1, keepdims=True) for a in (q, k))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H, dk))) * (
        20.0 if strong else 0.1)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    S0 = jax.random.normal(ks[5], (B, H, dk, dv))
    valid = jnp.asarray(valid if valid is not None else [S] * B)
    o, S1 = delta_rule_chunk(q, k, v, g, beta, S0, valid)
    state, outs = S0, []
    for t in range(S):
        o_t, new = delta_rule_step(
            q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], state)
        state = jnp.where((t < valid)[:, None, None, None], new, state)
        outs.append(o_t)
    real = (jnp.arange(S)[None, :] < valid[:, None])[:, :, None, None]
    assert bool(jnp.all(jnp.isfinite(o)))
    assert float(jnp.max(jnp.abs((o - jnp.stack(outs, 1)) * real))) < 2e-5
    assert float(jnp.max(jnp.abs(S1 - state))) < 2e-5


_CHUNK_CASES = [
    (5, 0, None), (16, 1, None), (48, 1, None), (64, 0, [1, 17, 64]),
    (128, 1, [0, 70, 128]), (200, 0, None),
]


def _head_decay_inputs(S, strong):
    B, H, dk, dv = 3, 2, 12, 24
    ks = jax.random.split(jax.random.PRNGKey(100 + S + strong), 6)
    q = jax.random.normal(ks[0], (B, S, H, dk))
    k = jax.random.normal(ks[1], (B, S, H, dk))
    q, k = (a / jnp.linalg.norm(a, axis=-1, keepdims=True) for a in (q, k))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (B, S, H))) * (
        20.0 if strong else 0.1)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    S0 = jax.random.normal(ks[5], (B, H, dk, dv))
    return q, k, v, g, beta, S0


@pytest.mark.parametrize("S,strong,valid", _CHUNK_CASES)
def test_the_chunk_form_with_a_decay_a_head_is_the_token_recurrence(
        S, strong, valid):
    """``g`` [B, S, H], key dim 12 and value dim 24: block and sub-block
    edges, a decay strong enough to overflow a split factor, ragged rows."""
    q, k, v, g, beta, S0 = _head_decay_inputs(S, strong)
    valid = jnp.asarray(valid if valid is not None else [S] * 3)
    o, S1 = delta_rule_chunk(q, k, v, g, beta, S0, valid)
    assert o.shape == v.shape and S1.shape == S0.shape
    state, outs = S0, []
    for t in range(S):
        o_t, new = delta_rule_step(
            q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], state)
        state = jnp.where((t < valid)[:, None, None, None], new, state)
        outs.append(o_t)
    real = (jnp.arange(S)[None, :] < valid[:, None])[:, :, None, None]
    assert bool(jnp.all(jnp.isfinite(o)))
    assert float(jnp.max(jnp.abs((o - jnp.stack(outs, 1)) * real))) < 2e-5
    assert float(jnp.max(jnp.abs(S1 - state))) < 2e-5


@pytest.mark.parametrize("S,strong,valid", _CHUNK_CASES)
def test_a_decay_a_head_is_the_channel_form_with_g_broadcast(S, strong, valid):
    """The two forms are told apart by the shape of ``g`` alone and are
    one mathematics: both chunk forms and both one-token steps agree."""
    q, k, v, g, beta, S0 = _head_decay_inputs(S, strong)
    wide = jnp.broadcast_to(g[..., None], k.shape)
    valid = jnp.asarray(valid if valid is not None else [S] * 3)
    real = (jnp.arange(S)[None, :] < valid[:, None])[:, :, None, None]
    o, S1 = delta_rule_chunk(q, k, v, g, beta, S0, valid)
    o_c, S1_c = delta_rule_chunk(q, k, v, wide, beta, S0, valid)
    assert float(jnp.max(jnp.abs((o - o_c) * real))) < 2e-5
    assert float(jnp.max(jnp.abs(S1 - S1_c))) < 2e-5
    a, b = (delta_rule_step(q[:, 0], k[:, 0], v[:, 0], x[:, 0], beta[:, 0], S0)
            for x in (g, wide))
    np.testing.assert_allclose(a[0], b[0], atol=1e-6)
    np.testing.assert_allclose(a[1], b[1], atol=1e-6)


@pytest.mark.parametrize("dv,held", [
    (24, (9, 128)), (128, (4, 12, 128)), (20, (4, 12, 20))])
def test_a_slots_state_is_held_lane_dense_and_read_from_its_shape(dv, held):
    """Value dims off the 128 lanes are held flat in rows of 128 (nothing to
    pad) where the slot's numbers fill whole rows, else ``[H, dk, dv]`` as
    whole tiles are; a step reads which from the shape, and each holds what
    the delta rule wrote."""
    cfg = dataclasses.replace(OLMO, num_layers=4, linear_attn=dataclasses.replace(
        OLMO.linear_attn, value_head_dim=dv))
    assert llama.state_slot_shape(cfg.linear_attn) == held
    cache = llama.make_cache(cfg, 16, PAGE, dtype=jnp.float32, state_slots=4)
    assert cache["state"].shape == (3, 4, *held)
    assert "stats" not in cache, "a dense model counts no expert share"
    assert set(llama.cache_specs(cfg)) == set(cache)
    p = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, 512)
    table = table_rows([(range(4), 2, -1)])
    _, cache = llama.prefill(
        p, cfg, jnp.pad(toks, ((0, 0), (0, 8))), jnp.asarray([24]), cache,
        table, dtype=jnp.float32)
    full = llama.forward_full(p, cfg, toks, dtype=jnp.float32)
    nxt = jnp.argmax(full[0, -1])[None]
    logits, cache = llama.decode_step(
        p, cfg, nxt, jnp.asarray([24]), cache, table, jnp.asarray([True]),
        dtype=jnp.float32)
    want = llama.forward_full(
        p, cfg, jnp.concatenate([toks, nxt[None]], 1), dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(logits[0] - want[0, -1]))) < OLMO_TOL
    assert float(jnp.max(jnp.abs(cache["state"][:, 2]))) > 0
    assert float(jnp.max(jnp.abs(cache["state"][:, 1]))) == 0.0
