"""Olmo-Hybrid (one decay a head, dk != dv, full-rank gates, post-norm; PR 33)
against its plain reference ``benchmarks/reference/olmo_hybrid.py`` (tests/
hybrid_state_common.py has the fixtures and the tolerances' reasons).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import olmo_hybrid as olmo_ref
from opsagent_tpu.models import llama
from hybrid_state_common import (  # noqa: F401 (fixtures)
    OLMO,
    OLMO_TOL,
    PAGE,
    TOL,
    _randomised,
    highest,
    release_compiled_programs,
    table_rows,
    tokens,
)


@pytest.fixture(scope="module")
def olmo_params():
    return _randomised(
        llama.init_params(OLMO, jax.random.PRNGKey(0), jnp.float32),
        jax.random.PRNGKey(7))


def olmo_logits(params, tokens, cfg=OLMO):
    """The reference's full forward pass over one sequence of tokens."""
    x = params["embed"][tokens].astype(jnp.float32)
    for p in range(cfg.num_layers // len(cfg.period_)):
        for key, mixer, n in llama.period_runs(cfg):
            for j in range(n):
                w = jax.tree.map(lambda a: a[p, j], params["layers"][key])
                x = olmo_ref.layer(
                    x, w, kind="full" if mixer == "attn" else "linear",
                    heads=cfg.num_heads,
                    linear_heads=cfg.linear_attn.num_heads,
                    eps=cfg.rms_norm_eps,
                    neg_eigval=cfg.linear_attn.neg_eigval)
    return olmo_ref.logits(x, params["final_norm"], params["lm_head"],
                           cfg.rms_norm_eps)


@pytest.fixture(scope="module")
def olmo_truth(olmo_params, tokens):
    return jnp.stack([olmo_logits(olmo_params, tokens[i]) for i in range(2)])


def olmo_cache(slots=8):
    return llama.make_cache(OLMO, 64, PAGE, dtype=jnp.float32, state_slots=slots)


def test_olmo_forward_full_is_the_reference(olmo_params, tokens, olmo_truth):
    full = llama.forward_full(olmo_params, OLMO, tokens, dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(full - olmo_truth))) < OLMO_TOL


def test_olmo_prefill_then_decode_through_pages_and_slots_is_the_reference(
        olmo_params, tokens, olmo_truth):
    """Prefill (the chunk form from slots), then 40 one-token steps (the
    recurrence), beside the same steps with the state rounded to bfloat16
    between them: the first within the tolerance, the second far outside."""
    cache = olmo_cache()
    table = table_rows([(range(8), 1, -1), (range(8, 16), 3, -1)])
    n = np.array([37, 32])
    padded = np.zeros((2, 64), np.int32)
    for i in range(2):
        padded[i, :n[i]] = np.asarray(tokens[i, :n[i]])
    logits, cache = llama.prefill(
        olmo_params, OLMO, jnp.asarray(padded), jnp.asarray(n), cache, table,
        dtype=jnp.float32)
    for i in range(2):
        assert float(jnp.max(jnp.abs(
            logits[i] - olmo_truth[i, n[i] - 1]))) < OLMO_TOL
    # one program for the 80 steps (an eager step compiles its layer scan
    # anew at every call)
    step = jax.jit(functools.partial(
        llama.decode_step, cfg=OLMO, dtype=jnp.float32))
    rounded = cache
    worst = worst_rounded = 0.0
    for _ in range(40):
        kw = dict(tokens=jnp.asarray([tokens[0, n[0]], tokens[1, n[1]]]),
                  lengths=jnp.asarray(n), page_table=table,
                  active=jnp.asarray([True, True]))
        logits, cache = step(olmo_params, cache=cache, **kw)
        low, rounded = step(olmo_params, cache=rounded, **kw)
        rounded = dict(rounded, state=rounded["state"].astype(
            jnp.bfloat16).astype(jnp.float32))
        for i in range(2):
            worst = max(worst, float(jnp.max(jnp.abs(
                logits[i] - olmo_truth[i, n[i]]))))
            worst_rounded = max(worst_rounded, float(jnp.max(jnp.abs(
                low[i] - olmo_truth[i, n[i]]))))
        n = n + 1
    assert worst < OLMO_TOL
    assert worst_rounded > 10 * OLMO_TOL, "a bfloat16 state would pass"


def test_olmo_mixed_steps_and_a_restored_snapshot_are_the_reference(
        olmo_params, tokens, olmo_truth):
    """A chunk row with a snapshot slot armed beside a shorter chunk row and
    an idle row; then a decode lane beside a chunk; then a second sequence
    that shares the first's pages, gets its snapshot copied in and prefills
    the rest: every logit is the reference's full forward pass."""
    cache = olmo_cache()
    table = table_rows([(range(8), 1, 6), (range(8, 16), 3, -1),
                        (range(16, 24), 5, -1)])
    first = np.zeros((3, 32), np.int32)
    first[0, :32] = np.asarray(tokens[0, :32])
    first[1, :20] = np.asarray(tokens[1, :20])
    _, cache = llama.mixed_step(
        olmo_params, OLMO, jnp.asarray(first), jnp.zeros((3,), jnp.int32),
        jnp.asarray([32, 20, 0]), cache, table, dtype=jnp.float32)
    # row 0 ended on a page boundary (32): its state is in slot 6 too
    np.testing.assert_array_equal(cache["state"][:, 6], cache["state"][:, 1])
    assert float(jnp.max(jnp.abs(cache["state"][:, 6]))) > 0
    assert float(jnp.max(jnp.abs(cache["state"][:, 5]))) == 0.0
    step = np.zeros((3, 16), np.int32)
    step[0, 0] = int(tokens[0, 32])
    step[1, :7] = np.asarray(tokens[1, 20:27])
    logits, cache = llama.mixed_step(
        olmo_params, OLMO, jnp.asarray(step), jnp.asarray([32, 20, 0]),
        jnp.asarray([1, 7, 0]), cache, table, dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(logits[0] - olmo_truth[0, 32]))) < OLMO_TOL
    assert float(jnp.max(jnp.abs(logits[1] - olmo_truth[1, 26]))) < OLMO_TOL
    cache = llama.copy_state_slots(cache, jnp.asarray([6]), jnp.asarray([2]))
    rest = np.zeros((1, 64), np.int32)
    rest[0, :45] = np.asarray(tokens[0, 32:77])
    logits, _ = llama.prefill_with_prefix(
        olmo_params, OLMO, jnp.asarray(rest), jnp.asarray([32]),
        jnp.asarray([45]), cache, table_rows([([0, 1, 30, 31, 32], 2, -1)]),
        dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(logits[0] - olmo_truth[0, 76]))) < OLMO_TOL


def test_the_packed_mixed_step_is_the_rows_step_in_the_post_norm_block(
        olmo_params, packed_against_rows, ragged_case):
    q_lens, S = ragged_case
    table = table_rows([(range(8 * i, 8 * i + 8), i, -1) for i in range(6)])
    packed_against_rows(OLMO, olmo_params, q_lens, S, TOL, table=table)


def test_two_olmo_turns_through_the_engine_are_the_references_choice():
    """Through ``Engine`` on the normal path: a turn (chunked prefill, fused
    decode blocks), the history re-sent after a trie hit that restores a
    state snapshot, then ``step_mixed_async`` at depth 2 with a decode lane
    riding beside a longer prompt's chunks. Every served token's logit, in
    the REFERENCE's full forward pass over prompt and reply, lies within
    the tolerance of the reference's best: the engine served the
    reference's model (two logits closer than the tolerance may swap)."""
    from opsagent_tpu import obs
    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    eng = Engine(EngineConfig(
        model="tiny-olmo-hybrid", dtype=jnp.float32, tp=1, max_batch_size=4,
        num_pages=128, max_pages_per_seq=32, prefill_buckets=(64,),
        mixed_buckets=(16, 32), max_step_tokens=64, decode_block=4,
        state_snapshots=3))
    assert "stats" not in eng.cache
    info = eng.impl_info()
    assert info["lin_decay"] == "head" and info["state_dtype"] == "float32"
    assert info["state_layout"] == [6, 9, 128]
    assert info["state_slot_bytes"] == 6 * 9 * 128 * 4
    rng = np.random.default_rng(0)
    sampling = SamplingParams(max_tokens=24, temperature=0.0)
    restored = "opsagent_state_restored_tokens_total"

    def served_is_the_references(prompt, out):
        truth = olmo_logits(eng.params, jnp.asarray(prompt + out))
        at = truth[len(prompt) - 1:len(prompt) - 1 + len(out)]
        gap = jnp.max(at, axis=-1) - at[jnp.arange(len(out)), jnp.asarray(out)]
        assert float(jnp.max(gap)) < OLMO_TOL

    def turn(prompt):
        out = eng.generate([prompt], sampling)[0]
        served_is_the_references(prompt, out)
        return out

    first = [int(x) for x in rng.integers(0, 500, size=90)]
    reply = turn(first)
    before = obs.metrics_snapshot().get(restored, 0.0)
    turn(first + reply + [int(x) for x in rng.integers(0, 500, size=30)])
    snap = obs.metrics_snapshot()
    assert snap[restored] - before == 112
    assert snap['opsagent_state_slot_bytes{part="state"}'] == 6 * 12 * 96 * 4
    assert snap['opsagent_decode_dispatches_total{kind="block"}'] > 0
    assert eng.alloc.state_slots_in_use()[0] == 0
    eng.sync_device_counters()      # nothing to read: no expert share
    # mixed steps: a decode lane beside the chunks of a longer prompt
    short = [int(x) for x in rng.integers(0, 500, size=5)]
    long = [int(x) for x in rng.integers(0, 500, size=70)]
    a = eng.add_request(short, SamplingParams(max_tokens=12, temperature=0.0))
    b = eng.begin_request(long, SamplingParams(max_tokens=6, temperature=0.0))
    for _ in range(200):
        if eng.sequences[a].done and eng.sequences[b].done:
            break
        chunks = {}
        if b in eng._prefilling:
            done, total = eng.prefill_progress(b)
            if total > done:
                chunks = {b: min(total - done, 16)}
        eng.step_mixed_async(
            [s for s in (a, b) if s not in eng._prefilling
             and not eng.sequences[s].done], chunks)
    eng.async_drain()
    lanes = "opsagent_mixed_dispatch_decode_lanes_count"
    assert obs.metrics_snapshot()[lanes] > 0
    served_is_the_references(short, eng.finish(a))
    served_is_the_references(long, eng.finish(b))
