"""Jamba's dense hybrid (Mamba-1 selective-scan layers over the state slots
beside multi-query NoPE attention over pages, a tied head) against the plain
reference ``benchmarks/reference/jamba.py`` at toy widths on seeded random
weights, float32: ``tiny-jamba``, two shortened periods ``mamba, mamba, attn,
mamba``.

Tolerances, and why. Program and reference compute the same float32
mathematics in another order (the state held ``[d_state, d_inner]`` and
summed over its sublanes against ``[d_inner, d_state]`` summed over its
last axis, the convolution continued from a tail against one pass over the
whole sequence, attention over pages against one block), so they differ by
rounding alone: logits of magnitude ~4 agree to 2e-4 absolute (measured
1.4e-5 at the worst; some ten times that, the tolerance of
``tests/hybrid_state_common.py``). A recurrent state held in bfloat16 between
steps errs by more than ten times the tolerance after a few dozen tokens
(asserted below): the comparison would catch it.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import jamba as ref
from opsagent_tpu.models import llama
from opsagent_tpu.models.config import (
    PRESETS, config_from_hf, hf_config_dict,
)
from opsagent_tpu.ops.selective_scan import selective_scan, selective_scan_step

TOL = 2e-4
CFG = PRESETS["tiny-jamba"]
PAGE = 16
MAXP = 16
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(autouse=True, scope="module")
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def release_compiled_programs():
    """Drop JAX's in-process caches of compiled programs once the process
    holds more than two fifths of the memory mappings it may have
    (``tests/hybrid_state_common.py`` has the reason)."""
    yield
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            limit = int(f.read())
        with open("/proc/self/maps") as f:
            held = sum(1 for _ in f)
    except (OSError, ValueError):
        return
    if held > 0.4 * limit:
        import gc

        jax.clear_caches()
        gc.collect()


def _randomised(tree, key):
    """``init_params`` leaves biases, the skip and the norms at zero or one
    and ``A_log`` the same in every channel; give them values, so that a
    dropped or transposed one shows."""
    out = {}
    for i, (name, leaf) in enumerate(sorted(tree.items())):
        k = jax.random.fold_in(key, i)
        if isinstance(leaf, dict):
            out[name] = _randomised(leaf, k)
        elif name == "a_log":
            out[name] = leaf + 0.3 * jax.random.normal(k, leaf.shape)
        elif name == "dt_bias":
            out[name] = jax.random.normal(k, leaf.shape) * 0.5 - 2.0
        elif name == "conv_b":
            out[name] = 0.2 * jax.random.normal(k, leaf.shape)
        elif name == "d_skip" or name.endswith("norm"):
            out[name] = 1 + 0.1 * jax.random.normal(k, leaf.shape)
        else:
            out[name] = leaf
    return out


@pytest.fixture(scope="module")
def params():
    return _randomised(
        llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32),
        jax.random.PRNGKey(7))


def layers_of(params, cfg=CFG):
    """(kind, float32 leaves) of every layer, in the model's order."""
    out = []
    for p in range(cfg.num_layers // len(cfg.period_)):
        for key, mixer, n in llama.period_runs(cfg):
            for j in range(n):
                out.append((
                    "attention" if mixer == "attn" else "mamba",
                    jax.tree.map(lambda a: a[p, j], params["layers"][key])))
    return out


def ref_logits(params, tokens, cfg=CFG):
    """The reference's full pass over one sequence, the head the embedding."""
    x = params["embed"][tokens].astype(jnp.float32)
    for kind, w in layers_of(params, cfg):
        x = ref.layer(x, w, kind=kind, heads=cfg.num_heads,
                      kv_heads=cfg.num_kv_heads, eps=cfg.rms_norm_eps)
    return ref.logits(x, params["final_norm"], params["embed"].T,
                      cfg.rms_norm_eps)


def table_rows(rows):
    """rows: [(pages, state slot, snapshot slot)] -> [B, MAXP + 2]."""
    t = np.full((len(rows), MAXP + 2), -1, np.int32)
    for i, (pages, slot, snap) in enumerate(rows):
        t[i, :len(pages)] = pages
        t[i, MAXP:] = slot, snap
    return jnp.asarray(t)


def fresh_cache(slots=8):
    return llama.make_cache(CFG, 64, PAGE, dtype=jnp.float32, state_slots=slots)


def padded_rows(rows, S):
    out = np.zeros((len(rows), S), np.int32)
    for i, r in enumerate(rows):
        out[i, :len(r)] = np.asarray(r)
    return jnp.asarray(out)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 100), 0, CFG.vocab_size)


@pytest.fixture(scope="module")
def truth(params, tokens):
    return jnp.stack([ref_logits(params, tokens[i]) for i in range(2)])


# -- the configuration ----------------------------------------------------------
def test_the_preset_is_the_published_model():
    cfg = PRESETS["jamba2-3b"]
    assert cfg.num_params() == 3_029_337_472
    assert cfg.mixer_period == ("mamba",) * 7 + ("attn",) + ("mamba",) * 6
    assert (cfg.count_mixers("mamba"), cfg.count_mixers("attn")) == (26, 2)
    assert [i for i in range(28) if cfg.mixer_of(i) == "attn"] == [7, 21]
    assert cfg.has_state and cfg.state_mixer == "mamba"
    assert (cfg.head_dim_, cfg.num_kv_heads, cfg.use_rope) == (128, 1, False)
    assert cfg.tie_embeddings and cfg.moe is None
    untied = PRESETS["jamba2-3b-untied"]
    assert untied.num_params() - cfg.num_params() == 65536 * 2560
    assert PRESETS["tiny-jamba"].state_mixer == "mamba"
    assert not PRESETS["tiny-olmo-hybrid"].mamba
    assert PRESETS["tiny-olmo-hybrid"].state_mixer == "linear"
    assert PRESETS["tiny-test"].state_mixer == ""


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_catalogs_config_round_trips_through_the_hf_mapping(tmp_path):
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"AI21-Jamba2-3B"' in line)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(row["config"]))
    cfg = config_from_hf(str(path), name="jamba2-3b")
    assert cfg == PRESETS["jamba2-3b"]
    back = hf_config_dict(cfg)
    assert {k: back[k] for k in row["config"]} == row["config"]
    path.write_text(json.dumps(back))
    assert config_from_hf(str(path), name="jamba2-3b") == cfg


@pytest.mark.parametrize("change,said", [
    ({"num_experts": 16}, "num_experts=16"),
    ({"sliding_window": 4096}, "sliding_window"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"num_hidden_layers": 20}, "whole periods"),
])
def test_what_the_jamba_mapping_cannot_run_is_refused_by_name(
        tmp_path, change, said):
    hf = dict(hf_config_dict(PRESETS["jamba2-3b"]), **change)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(hf))
    with pytest.raises(ValueError, match=said):
        config_from_hf(str(path))


def test_one_kind_of_state_a_model():
    import dataclasses

    with pytest.raises(ValueError, match="mamba unset"):
        dataclasses.replace(CFG, mamba=None)
    with pytest.raises(ValueError, match="one kind of recurrent state"):
        dataclasses.replace(
            CFG, mixer_period=("mamba", "linear", "attn", "mamba"),
            linear_attn=PRESETS["tiny-hybrid"].linear_attn)


def test_the_tree_its_specs_and_the_held_state(params):
    from jax.sharding import PartitionSpec

    specs = llama.param_specs(CFG)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec))
    assert "lm_head" not in params, "the head is the embedding"
    assert [r[0] for r in llama.period_runs(CFG)] == [
        "r0_mamba", "r1_attn", "r2_mamba"]
    run = params["layers"]["r0_mamba"]
    assert run["a_log"].shape == (2, 2, 16, 128)       # [d_state, d_inner]
    assert run["m_in"].shape == (2, 2, 64, 256)
    assert run["m_x"].shape == (2, 2, 128, 8 + 32)
    cache = fresh_cache(slots=5)
    # 6 Mamba layers; pages for the 2 attention layers alone, one kv head
    assert cache["state"].shape == (6, 5, 16, 128)
    assert cache["state"].dtype == jnp.float32
    assert cache["conv"].shape == (6, 5, 3 * 128)
    assert cache["k"].shape == (2, 64, PAGE, 1, 16)
    full = PRESETS["jamba2-3b"]
    state, conv = llama.slot_shapes(full)
    assert (state, conv) == ((16, 5120), (15360,))
    assert 26 * (16 * 5120 * 4 + 15360 * 2) == 9_318_400


# -- the scan -------------------------------------------------------------------
def _scan_inputs(B, S, C=128, N=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, S, C))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, C)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (N, C)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    h0 = jax.random.normal(ks[5], (B, N, C))
    return x, dt, A, Bm, Cm, h0


@pytest.mark.parametrize("S,valid", [
    (1, (1, 0, 1)), (5, (5, 2, 0)), (16, (16, 7, 1)), (33, (20, 33, 0))])
def test_the_scan_is_the_one_token_recurrence(S, valid):
    """A scan over a row's slots equals the one-token step applied to the
    row's valid tokens in turn, and a slot past ``valid`` leaves the state
    as it was, to the bit."""
    x, dt, A, Bm, Cm, h0 = _scan_inputs(3, S)
    y, h1 = selective_scan(x, dt, A, Bm, Cm, h0, jnp.asarray(valid))
    for b, n in enumerate(valid):
        h = h0[b:b + 1]
        for t in range(n):
            want, h = selective_scan_step(
                x[b:b + 1, t], dt[b:b + 1, t], A, Bm[b:b + 1, t],
                Cm[b:b + 1, t], h)
            assert float(jnp.max(jnp.abs(y[b, t] - want[0]))) < 1e-5
        if n == 0:
            np.testing.assert_array_equal(h1[b], h0[b])
        else:
            assert float(jnp.max(jnp.abs(h1[b] - h[0]))) < 1e-5


def test_the_scan_is_the_references_recurrence():
    """Against the equations as the reference writes them, the state in
    the published orientation ``[d_inner, d_state]``."""
    x, dt, A, Bm, Cm, _ = _scan_inputs(1, 40, seed=3)
    y, h1 = selective_scan(
        x, dt, A, Bm, Cm, jnp.zeros((1, 16, 128)), jnp.asarray([40]))
    h = np.zeros((128, 16))
    for t in range(40):
        h = (np.exp(np.asarray(dt[0, t])[:, None] * np.asarray(A).T) * h
             + np.asarray(dt[0, t] * x[0, t])[:, None] * np.asarray(Bm[0, t]))
        np.testing.assert_allclose(
            y[0, t], h @ np.asarray(Cm[0, t]), atol=1e-4)
    np.testing.assert_allclose(h1[0], h.T, atol=1e-4)


# -- the model against the reference --------------------------------------------
def test_forward_full_is_the_reference(params, tokens, truth):
    full = llama.forward_full(params, CFG, tokens, dtype=jnp.float32)
    assert full.shape == (2, 100, CFG.vocab_size)
    assert float(jnp.max(jnp.abs(full - truth))) < TOL


@pytest.mark.parametrize("chunks", [
    (37,), (1, 36), (2, 3, 32), (16, 16, 5), (5, 1, 1, 30), (7,) * 5 + (2,)])
def test_prefill_in_chunks_then_decode_is_the_reference(
        params, tokens, truth, chunks):
    """37 tokens admitted in chunks (ends inside a conv window, chunks of
    one), each continuing from the slot's state and tail, then decode
    through pages and slots: every logit the reference's full pass."""
    cache = fresh_cache()
    table = table_rows([(range(8), 1, -1), (range(8, 16), 3, -1)])
    done = 0
    for i, c in enumerate(chunks):
        rows = padded_rows([tokens[b, done:done + c] for b in range(2)], 64)
        if i == 0:
            logits, cache = llama.prefill(
                params, CFG, rows, jnp.asarray([c, c]), cache, table,
                dtype=jnp.float32)
        else:
            logits, cache = llama.prefill_with_prefix(
                params, CFG, rows, jnp.asarray([done, done]),
                jnp.asarray([c, c]), cache, table, dtype=jnp.float32)
        done += c
        for b in range(2):
            assert float(jnp.max(jnp.abs(logits[b] - truth[b, done - 1]))) < TOL
    for _ in range(6):
        logits, cache = llama.decode_step(
            params, CFG, tokens[:, done], jnp.asarray([done, done]), cache,
            table, jnp.asarray([True, True]), dtype=jnp.float32)
        for b in range(2):
            assert float(jnp.max(jnp.abs(logits[b] - truth[b, done]))) < TOL
        done += 1


def test_a_bfloat16_state_between_steps_fails_the_tolerance(
        params, tokens, truth):
    """What holds the state float32: the same decode steps with the state
    rounded to bfloat16 between them err by more than ten tolerances."""
    cache = fresh_cache()
    table = table_rows([(range(8), 1, -1), (range(8, 16), 3, -1)])
    n = np.array([37, 32])
    rows = padded_rows([tokens[i, :n[i]] for i in range(2)], 64)
    _, cache = llama.prefill(
        params, CFG, rows, jnp.asarray(n), cache, table, dtype=jnp.float32)
    rounded = cache
    worst = worst_rounded = 0.0
    for _ in range(40):
        feed = jnp.asarray([tokens[0, n[0]], tokens[1, n[1]]])
        args = (jnp.asarray(n), table, jnp.asarray([True, True]))
        logits, cache = llama.decode_step(
            params, CFG, feed, args[0], cache, *args[1:], dtype=jnp.float32)
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


def test_a_mixed_step_leaves_a_padded_rows_state_and_tail_untouched(
        params, tokens, truth):
    """Decode lanes and prefill lanes at unlike positions in one dispatch:
    each row gets its own tokens' update; an idle row (q_len 0), a slot no
    row holds and the slots of a row past its ``valid`` get nothing."""
    cache = fresh_cache()
    cache = dict(cache, state=cache["state"].at[:, 5].set(7.0),
                 conv=cache["conv"].at[:, 5].set(3.0))
    table = table_rows([(range(8), 1, -1), (range(8, 16), 3, -1),
                        (range(16, 24), 5, -1)])
    rows = padded_rows([tokens[0, :32], tokens[1, :20], []], 32)
    _, cache = llama.mixed_step(
        params, CFG, rows, jnp.zeros((3,), jnp.int32),
        jnp.asarray([32, 20, 0]), cache, table, dtype=jnp.float32)
    assert float(jnp.min(cache["state"][:, 5])) == 7.0
    assert float(jnp.min(cache["conv"][:, 5])) == 3.0
    assert float(jnp.max(jnp.abs(cache["state"][:, 7]))) == 0.0
    # row 1 was given 20 of 32 slots: its state and tail are those of a
    # pass over the 20 alone (12 padded slots moved nothing)
    alone = fresh_cache()
    _, alone = llama.mixed_step(
        params, CFG, padded_rows([tokens[1, :20]], 20),
        jnp.zeros((1,), jnp.int32), jnp.asarray([20]), alone,
        table_rows([(range(8, 16), 3, -1)]), dtype=jnp.float32)
    for part in ("state", "conv"):
        assert float(jnp.max(jnp.abs(
            cache[part][:, 3] - alone[part][:, 3]))) < TOL
    # next: row 0 decodes one token, row 1 prefills 7 more of a 16-bucket
    step = padded_rows([tokens[0, 32:33], tokens[1, 20:27], []], 16)
    logits, cache = llama.mixed_step(
        params, CFG, step, jnp.asarray([32, 20, 0]),
        jnp.asarray([1, 7, 0]), cache, table, dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(logits[0] - truth[0, 32]))) < TOL
    assert float(jnp.max(jnp.abs(logits[1] - truth[1, 26]))) < TOL
    assert float(jnp.min(cache["state"][:, 5])) == 7.0
    assert float(jnp.min(cache["conv"][:, 5])) == 3.0


def test_the_packed_mixed_step_is_the_rows_step(
        params, packed_against_rows, ragged_case):
    """``W_in``, the gate, the output projection and the MLP over packed
    tokens, the conv and the scan over rows: pages, state, conv tails and
    logits are those of the step over rows."""
    q_lens, S = ragged_case
    table = table_rows([(range(8 * i, 8 * i + 8), i, -1) for i in range(6)])
    packed_against_rows(CFG, params, q_lens, S, TOL, table=table)


def test_a_restored_snapshot_and_the_rest_equal_prefilling_it_all(
        params, tokens, truth):
    """Row 0 prefills 48 tokens (three pages) with a snapshot slot armed:
    the pass leaves it on a page boundary, so state and tail are copied. A
    second sequence shares those pages, has the snapshot copied into its
    slot and prefills the rest: its logits are those of the same session
    from scratch, which are the reference's."""
    cache = fresh_cache()
    _, cache = llama.prefill(
        params, CFG, padded_rows([tokens[0, :48]], 64), jnp.asarray([48]),
        cache, table_rows([(range(8), 0, 6)]), dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(cache["state"][:, 6]))) > 0
    for part in ("state", "conv"):
        np.testing.assert_array_equal(cache[part][:, 6], cache[part][:, 0])
    cache = llama.copy_state_slots(
        cache, jnp.asarray([6, 6]), jnp.asarray([2, -1]))
    rest = padded_rows([tokens[0, 48:78]], 64)
    logits, cache = llama.prefill_with_prefix(
        params, CFG, rest, jnp.asarray([48]), jnp.asarray([30]),
        cache, table_rows([([0, 1, 2, 20, 21], 2, -1)]), dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(logits[0] - truth[0, 77]))) < TOL
    scratch, _ = llama.prefill(
        params, CFG, padded_rows([tokens[0, :78]], 128), jnp.asarray([78]),
        fresh_cache(), table_rows([(range(8), 0, -1)]), dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(logits[0] - scratch[0]))) < TOL
    # a pass that does not end on a page boundary writes no snapshot
    assert float(jnp.max(jnp.abs(cache["state"][:, 7]))) == 0.0


def test_a_fused_decode_block_is_the_single_steps(params, tokens, truth):
    """Eight greedy passes under one scan, pages and slots its carry: the
    tokens and the state it leaves are those of eight single steps, and
    each token is the reference's own first choice."""
    from opsagent_tpu.serving import decode_loop

    table = table_rows([(range(8), 1, -1), (range(8, 16), -1, -1)])
    cache = fresh_cache()
    logits, cache = llama.prefill(
        params, CFG, padded_rows([tokens[0, :21]], 64), jnp.asarray([21]),
        cache, table[:1], dtype=jnp.float32)
    first = int(jnp.argmax(logits[0]))
    single, served = cache, [first]
    for i in range(8):
        lg, single = llama.decode_step(
            params, CFG, jnp.asarray([served[-1], 0]),
            jnp.asarray([21 + i, 0]), single, table,
            jnp.asarray([True, False]), dtype=jnp.float32)
        served.append(int(jnp.argmax(lg[0])))
    tok, at = jnp.asarray([first, 0]), jnp.asarray([21, 0])
    active = jnp.asarray([True, False])
    toks, block, _ = decode_loop.decode_block_carry(    # every lane seated anew
        params, CFG, tok, at, jnp.zeros_like(active), jax.random.PRNGKey(0),
        jnp.ones_like(active), tok, at, active, jnp.asarray([8, 0]), cache,
        table, jnp.zeros((2,)), jnp.zeros((2,), jnp.int32), jnp.ones((2,)),
        jnp.int32(-1), jnp.int32(0), n_steps=8, greedy=True,
        dtype=jnp.float32)
    assert np.asarray(toks[0]).tolist() == served[1:]
    for part in ("state", "conv"):
        assert float(jnp.max(jnp.abs(block[part] - single[part]))) < 1e-5
    want = ref_logits(
        params, jnp.asarray([*np.asarray(tokens[0, :21]), *served[:-1]]))
    gaps = [float(want[20 + i].max() - want[20 + i][t])
            for i, t in enumerate(served)]
    assert max(gaps) < TOL, gaps


# -- through the engine ------------------------------------------------------------
def test_two_turns_through_the_engine_are_the_references_choice():
    """``--model-name tiny-jamba`` on the normal path: a turn (chunked
    prefill, fused decode blocks), then the history re-sent after a trie hit
    that restores a state snapshot. Every served token's logit, in the
    REFERENCE's full pass over prompt and reply, lies within the tolerance
    of the reference's best (two logits closer than that may swap)."""
    from opsagent_tpu import obs
    from opsagent_tpu.serving.engine import BackendRefused, Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    shape = dict(
        model="tiny-jamba", dtype=jnp.float32, tp=1, max_batch_size=4,
        num_pages=128, max_pages_per_seq=32, prefill_buckets=(64,),
        mixed_buckets=(16, 32), max_step_tokens=64, decode_block=4,
        state_snapshots=3)
    eng = Engine(EngineConfig(**shape))
    info = eng.impl_info()
    assert info["state_mixer"] == "mamba" and info["state_impl"] == "xla"
    assert info["state_dtype"] == "float32" and "lin_decay" not in info
    assert info["state_layout"] == [6, 16, 128]
    assert info["state_slot_bytes"] == 6 * 16 * 128 * 4
    assert info["conv_slot_bytes"] == 6 * 3 * 128 * 4
    assert "lm_head" not in eng.params
    rng = np.random.default_rng(0)
    sampling = SamplingParams(max_tokens=24, temperature=0.0)
    restored = "opsagent_state_restored_tokens_total"
    steps = 'opsagent_ssm_scan_steps_total{kind="%s"}'

    def turn(prompt):
        out = eng.generate([prompt], sampling)[0]
        want = ref_logits(eng.params, jnp.asarray(prompt + out))
        at = want[len(prompt) - 1:len(prompt) - 1 + len(out)]
        gap = jnp.max(at, axis=-1) - at[jnp.arange(len(out)), jnp.asarray(out)]
        assert float(jnp.max(gap)) < TOL
        return out

    first = [int(x) for x in rng.integers(0, 500, size=90)]
    reply = turn(first)
    snap = obs.metrics_snapshot()
    before = snap.get(restored, 0.0)
    real = snap[steps % "real"]
    # 90 prompt tokens and the 23 reply tokens that went back in, 6 layers
    assert real == (90 + 23) * 6 and snap[steps % "computed"] > real
    turn(first + reply + [int(x) for x in rng.integers(0, 500, size=30)])
    snap = obs.metrics_snapshot()
    assert snap[restored] - before == 112
    assert snap['opsagent_state_slot_bytes{part="state"}'] == 6 * 16 * 128 * 4
    assert snap['opsagent_decode_dispatches_total{kind="block"}'] > 0
    assert eng.alloc.state_slots_in_use()[0] == 0
    # what the engine refuses for a model with state stays refused, by name
    # (tp=2 never reaches the refusal: one kv head does not divide over two
    # shards, and the engine falls back to tp=1 before it)
    for change, said in (
            ({"offload": True}, "offload=True"),
            ({"weight_stream": "pallas-dma", "quantize": "int8"},
             "pallas-dma")):
        with pytest.raises(BackendRefused, match=said):
            Engine(EngineConfig(**dict(shape, **change)))
    with pytest.raises(BackendRefused, match="Mamba layers"):
        eng.snapshot("/nonexistent")
