"""A model of unlike layers against the plain reference (tests/
hybrid_state_common.py has the model, the reference and the tolerances): a
prompt prefilled and then decoded token by token through pages and state
slots gives the reference's logits at every position.
"""

import jax.numpy as jnp
import numpy as np

from opsagent_tpu.models import llama
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


def test_prefill_then_decode_through_pages_and_slots_is_the_reference(
        params, tokens, truth):
    cache = fresh_cache()
    table = table_rows([(range(8), 1, -1), (range(8, 16), 3, -1)])
    n = np.array([37, 32])
    padded = np.zeros((2, 64), np.int32)
    for i in range(2):
        padded[i, :n[i]] = np.asarray(tokens[i, :n[i]])
    logits, cache = llama.prefill(
        params, CFG, jnp.asarray(padded), jnp.asarray(n), cache, table,
        dtype=jnp.float32)
    for i in range(2):
        assert float(jnp.max(jnp.abs(logits[i] - truth[i, n[i] - 1]))) < TOL
    rounded = cache
    worst = worst_rounded = 0.0
    for _ in range(40):
        feed = jnp.asarray([tokens[0, n[0]], tokens[1, n[1]]])
        args = (jnp.asarray(n), table, jnp.asarray([True, True]))
        logits, cache = llama.decode_step(
            params, CFG, feed, args[0], cache, *args[1:], dtype=jnp.float32)
        # the same steps with the state held in bfloat16 between them
        low, rounded = llama.decode_step(
            params, CFG, feed, args[0], rounded, *args[1:], dtype=jnp.float32)
        rounded = dict(rounded, state=rounded["state"].astype(
            jnp.bfloat16).astype(jnp.float32))
        for i in range(2):
            worst = max(worst, float(jnp.max(jnp.abs(
                logits[i] - truth[i, n[i]]))))
            worst_rounded = max(worst_rounded, float(jnp.max(jnp.abs(
                low[i] - truth[i, n[i]]))))
        n = n + 1
    assert worst < TOL
    assert worst_rounded > 10 * TOL, "a bfloat16 state would pass"
