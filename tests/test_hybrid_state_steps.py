"""A model of unlike layers against the plain reference (tests/
hybrid_state_common.py has the model, the reference and the tolerances): the
whole forward pass, the conv's tail, mixed steps over ragged rows (rows and
packed), and a restored snapshot.
"""

import jax
import jax.numpy as jnp
import numpy as np

from opsagent_tpu.models import llama
from opsagent_tpu.ops.linear_attention import conv_with_tail
from hybrid_state_common import (  # noqa: F401 (fixtures)
    CFG,
    TOL,
    fresh_cache,
    highest,
    params,
    release_compiled_programs,
    table_rows,
    tokens,
    truth,
)


def test_forward_full_is_the_reference(params, tokens, truth):
    full = llama.forward_full(params, CFG, tokens, dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(full - truth))) < TOL


def test_the_conv_continues_from_the_rows_own_tail():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 6))
    w = jax.random.normal(jax.random.PRNGKey(4), (4, 6))
    whole, _ = conv_with_tail(x, jnp.zeros((2, 3, 6)), w, jnp.asarray([12, 12]))
    first, tail = conv_with_tail(
        x[:, :8], jnp.zeros((2, 3, 6)), w, jnp.asarray([5, 8]))
    # row 0 had 5 real positions of 8: its tail is inputs 2..4
    np.testing.assert_allclose(tail[0], x[0, 2:5], rtol=1e-6)
    rest, _ = conv_with_tail(x[:, 8:], tail, w, jnp.asarray([4, 4]))
    np.testing.assert_allclose(rest[1], whole[1, 8:], atol=1e-5)


def test_a_mixed_step_leaves_a_padded_rows_state_untouched(params, tokens, truth):
    """Decode lanes and a prefill lane in one dispatch: each row gets its
    own tokens' update, an idle row (q_len 0) and an unused slot nothing."""
    cache = fresh_cache()
    marked = cache["state"].at[:, 5].set(7.0)
    cache = dict(cache, state=marked, conv=cache["conv"].at[:, 5].set(3.0))
    table = table_rows([(range(8), 1, -1), (range(8, 16), 3, -1),
                        (range(16, 24), 5, -1)])
    padded = np.zeros((3, 32), np.int32)
    padded[0, :32] = np.asarray(tokens[0, :32])
    padded[1, :20] = np.asarray(tokens[1, :20])
    _, cache = llama.mixed_step(
        params, CFG, jnp.asarray(padded), jnp.zeros((3,), jnp.int32),
        jnp.asarray([32, 20, 0]), cache, table, dtype=jnp.float32)
    assert float(jnp.min(cache["state"][:, 5])) == 7.0
    assert float(jnp.min(cache["conv"][:, 5])) == 3.0
    assert float(jnp.max(jnp.abs(cache["state"][:, 7]))) == 0.0
    # next: row 0 decodes one token, row 1 prefills 7 more of a 16-bucket
    step = np.zeros((3, 16), np.int32)
    step[0, 0] = int(tokens[0, 32])
    step[1, :7] = np.asarray(tokens[1, 20:27])
    logits, cache = llama.mixed_step(
        params, CFG, jnp.asarray(step), jnp.asarray([32, 20, 0]),
        jnp.asarray([1, 7, 0]), cache, table, dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(logits[0] - truth[0, 32]))) < TOL
    assert float(jnp.max(jnp.abs(logits[1] - truth[1, 26]))) < TOL
    assert float(jnp.min(cache["state"][:, 5])) == 7.0


def test_the_packed_mixed_step_is_the_rows_step(
        params, packed_against_rows, ragged_case):
    """Tokens packed for the norms, projections and experts, rows for the
    page write, attention, the conv tail and the scan: pages, state, conv
    tails, expert counts and logits are those of the step over rows."""
    q_lens, S = ragged_case
    table = table_rows([(range(8 * i, 8 * i + 8), i, -1) for i in range(6)])
    packed_against_rows(CFG, params, q_lens, S, TOL, table=table)


def test_a_restored_snapshot_and_the_rest_equal_prefilling_it_all(
        params, tokens, truth):
    """Row 0 prefills 48 tokens (three pages) with a snapshot slot armed:
    the pass leaves it on a page boundary, so the state is copied. A second
    sequence shares those pages, has the snapshot copied into its slot and
    prefills the rest: its logits are those of prefilling everything."""
    cache = fresh_cache()
    first = np.zeros((1, 64), np.int32)
    first[0, :48] = np.asarray(tokens[0, :48])
    _, cache = llama.prefill(
        params, CFG, jnp.asarray(first), jnp.asarray([48]), cache,
        table_rows([(range(8), 0, 6)]), dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(cache["state"][:, 6]))) > 0
    np.testing.assert_array_equal(cache["state"][:, 6], cache["state"][:, 0])
    cache = llama.copy_state_slots(
        cache, jnp.asarray([6, 6]), jnp.asarray([2, -1]))
    rest = np.zeros((1, 64), np.int32)
    rest[0, :30] = np.asarray(tokens[0, 48:78])
    logits, cache = llama.prefill_with_prefix(
        params, CFG, jnp.asarray(rest), jnp.asarray([48]), jnp.asarray([30]),
        cache, table_rows([([0, 1, 2, 20, 21], 2, -1)]), dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(logits[0] - truth[0, 77]))) < TOL
    # a pass that does not end on a page boundary writes no snapshot
    assert float(jnp.max(jnp.abs(cache["state"][:, 7]))) == 0.0
