"""A model of unlike layers (softmax attention over pages beside delta-rule
linear attention over a recurrent state, experts at one chip's share),
against the plain reference ``benchmarks/reference/solar_open2.py`` at toy
widths on seeded random weights, float32.

Tolerances, and why. Program and reference compute the same float32
mathematics in another order (chunk form against token recurrence, sorted
expert dispatch against a loop over experts), so they differ by rounding
alone: logits of magnitude ~5 agree to 2e-4 absolute (measured 4e-5 at the
worst; five times that). A recurrent state held in bfloat16 between steps
errs by 1e-2 and more after a few dozen tokens (asserted below), fifty
times the tolerance: the comparison would catch it.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import solar_open2 as ref
from opsagent_tpu.models import llama
from opsagent_tpu.models.config import PRESETS
from opsagent_tpu.ops.linear_attention import (
    conv_with_tail, delta_rule_chunk, delta_rule_step,
)
from opsagent_tpu.serving.kvcache import PageAllocator

TOL = 2e-4
CFG = PRESETS["tiny-hybrid"]
PAGE = 16
MAXP = 16


@pytest.fixture(autouse=True, scope="module")
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def release_compiled_programs():
    """After each test of this file, drop JAX's in-process caches of
    compiled programs once the process holds more than two fifths of the
    memory mappings it may have. On the CPU every compiled program, each
    eager operation's too, holds several mappings of its own, a process
    may hold ``vm.max_map_count`` of them (65,530 here), and past that the
    next compile or the next write to the persistent compile cache dies
    with a segmentation fault or an abort: this file alone reached 59,574
    with PR 34's cases in it and died in its last test, where the parent's
    stopped some thousands short (one of its tests alone adds 28,000).
    ``jax.clear_caches()`` gives them back (30,843 -> 711 after that test);
    what is needed again is read back from the persistent cache or
    compiled again. No test here counts compiles across tests."""
    yield
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            limit = int(f.read())
        with open("/proc/self/maps") as f:
            held = sum(1 for _ in f)
    except (OSError, ValueError):
        return      # no such files: not Linux, nothing known to guard
    if held > 0.4 * limit:
        import gc

        jax.clear_caches()
        gc.collect()


def _randomised(tree, key):
    """``init_params`` leaves decay rates, offsets, biases and norms at
    zero or one; give them values, so that a dropped one shows."""
    out = {}
    for i, (name, leaf) in enumerate(sorted(tree.items())):
        k = jax.random.fold_in(key, i)
        if isinstance(leaf, dict):
            out[name] = _randomised(leaf, k)
        elif name == "a_log":
            out[name] = jnp.log(jax.random.uniform(
                k, leaf.shape, minval=1.0, maxval=16.0))
        elif name == "dt_bias":
            out[name] = jax.random.normal(k, leaf.shape) * 0.5 - 2.0
        elif name == "router_bias":
            out[name] = jax.random.normal(k, leaf.shape) * 0.3
        elif name.endswith("norm"):
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
                    "gqa" if mixer == "attn" else "linear",
                    jax.tree.map(lambda a: a[p, j], params["moe_layers"][key])))
    return out


def ref_layer(x, kind, w, cfg=CFG, held=None):
    m = cfg.moe
    return ref.layer(
        x, w, kind=kind, heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
        linear_heads=cfg.linear_attn.num_heads, top_k=m.num_experts_per_token,
        scale=m.routed_scaling_factor, eps=cfg.rms_norm_eps,
        neg_eigval=cfg.linear_attn.neg_eigval,
        held=held or (m.first_expert, m.num_experts))


def ref_logits(params, tokens, cfg=CFG):
    x = params["embed"][tokens].astype(jnp.float32)
    for kind, w in layers_of(params, cfg):
        x = ref_layer(x, kind, w, cfg)
    return ref.logits(x, params["final_norm"], params["lm_head"],
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


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 100), 0, CFG.vocab_size)


@pytest.fixture(scope="module")
def truth(params, tokens):
    return jnp.stack([ref_logits(params, tokens[i]) for i in range(2)])


def test_forward_full_is_the_reference(params, tokens, truth):
    full = llama.forward_full(params, CFG, tokens, dtype=jnp.float32)
    assert float(jnp.max(jnp.abs(full - truth))) < TOL


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


def test_the_eight_shares_and_the_shared_expert_once_are_the_uncut_layer(params):
    """One layer's expert MLP at each of two shares of four experts, the
    program's and the reference's: the routed parts of all shares plus the
    shared expert counted once are what the uncut layer (all eight experts
    held) gives."""
    m = CFG.moe
    w = layers_of(params)[1][1]
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 24, CFG.hidden_size))
    key = jax.random.PRNGKey(11)
    both = {name: jnp.concatenate([w[name], 0.1 * jax.random.normal(
        jax.random.fold_in(key, i), w[name].shape)])
        for i, name in enumerate(("eg", "eu", "ed"))}
    whole_cfg = dataclasses.replace(CFG, moe=dataclasses.replace(
        m, num_experts=8, router_experts=8))
    whole, stats = llama._moe_share(h, dict(w, **both), whole_cfg, None)
    assert float(stats[2]) == 0.0 and float(stats[1]) == 24 * 2
    shared = llama._shared_experts(h, w)
    routed = 0.0
    for first in (0, 4):
        part_cfg = dataclasses.replace(CFG, moe=dataclasses.replace(
            m, first_expert=first))
        part = {name: both[name][first:first + 4] for name in both}
        got, _ = llama._moe_share(h, dict(w, **part), part_cfg, None)
        want = ref.experts(
            h[0], dict(w, **part), top_k=2, scale=1.0, held=(first, 4))
        assert float(jnp.max(jnp.abs(got[0] - want))) < 1e-5
        routed = routed + (got - shared)
    assert float(jnp.max(jnp.abs(routed + shared - whole))) < 1e-5
    uncut = ref.experts(h[0], dict(w, **both), top_k=2, scale=1.0, held=(0, 8))
    assert float(jnp.max(jnp.abs(whole[0] - uncut))) < 1e-5


def test_the_expert_share_drops_no_assignment_at_any_token_count(params):
    """Every token routed to ONE held expert (the worst skew) at token
    counts on both sides of the block sizes: nothing is dropped."""
    w = dict(layers_of(params)[0][1])
    w["router"] = jnp.zeros_like(w["router"])
    w["router_bias"] = jnp.zeros_like(w["router_bias"]).at[2].set(9.0).at[1].set(5.0)
    for T in (1, 7, 33, 300):
        h = jax.random.normal(jax.random.PRNGKey(T), (1, T, CFG.hidden_size))
        got, stats = llama._moe_share(h, w, CFG, None)
        want = ref.experts(h[0], w, top_k=2, scale=1.0, held=(0, 4))
        assert float(jnp.max(jnp.abs(got[0] - want))) < 1e-5
        assert float(stats[1]) == 2 * T and float(stats[4]) == T


_RNG = np.random.default_rng(43)
BLOCK_SIZES = {
    # per-expert assignment counts that a block-to-expert map can get wrong
    "idle-at-the-start": (8, [0, 0, 5, 9]),
    "idle-in-the-middle": (8, [3, 0, 0, 17]),
    "idle-at-the-end": (8, [7, 1, 0, 0]),
    "idle-between-every-two": (8, [0, 9, 0, 1, 0, 0, 24, 0]),
    "one-holds-all": (8, [0, 40, 0, 0]),
    "the-last-holds-all": (16, [0] * 63 + [1024]),
    "nothing-lands-here": (8, [0, 0, 0, 0]),
    "whole-blocks": (8, [8, 16, 8, 24]),
    "whole-blocks-and-gaps": (16, [0, 32, 0, 16, 16, 0]),
    "one-each": (16, [1] * 64),
    # a tick's counts at the cells' means, some experts without work
    "cell-5-like": (16, _RNG.poisson(10.9, 64) * (_RNG.random(64) > 0.3)),
    "cell-3-like": (8, _RNG.poisson(4.7, 40) * (_RNG.random(40) > 0.2)),
}


@pytest.mark.parametrize("case", list(BLOCK_SIZES))
def test_the_block_map_is_the_search_it_replaced_block_for_block(case):
    """``_block_experts`` against the per-block binary search that
    ``one_block`` ran before (``searchsorted(block_ends, b, "right")``), on
    the plan's own arithmetic: the same expert for every block in use, and
    ``E`` (no expert) for every block past them, as the search gave too."""
    bm, sizes = BLOCK_SIZES[case]
    sizes = np.asarray(sizes)
    E = len(sizes)
    padded = -(-sizes // bm) * bm
    block_ends = np.cumsum(padded) // bm
    blocks_used = int(block_ends[-1])
    blocks = blocks_used + E + 3        # the buffer holds every case
    got = np.asarray(llama._block_experts(
        jnp.asarray(block_ends, jnp.int32), blocks))
    assert got.dtype == np.int32 and got.shape == (blocks,)
    np.testing.assert_array_equal(got, jnp.searchsorted(
        jnp.asarray(block_ends), jnp.arange(blocks), side="right"))
    assert (got[blocks_used:] == E).all()
    # and by the definition: a block in use lies inside its expert's rows
    for b in range(blocks_used):
        e = got[b]
        assert block_ends[e] - padded[e] // bm <= b < block_ends[e]
        assert sizes[e] > 0


def routed_to(w, pairs):
    """A layer's weights ``w`` with a router, and an input, whose tokens go
    where ``pairs`` says: ``[((expert, expert), tokens), ...]`` by the
    router's own numbering over its width of 8. Token classes are one-hot
    on the first hidden dimensions and the router reads those alone, so
    the choice is exact."""
    T = sum(n for _, n in pairs)
    router = np.zeros((CFG.hidden_size, 8), np.float32)
    h = np.array(jax.random.normal(jax.random.PRNGKey(3), (T, CFG.hidden_size)))
    h[:, :len(pairs)] = 0.0
    at = 0
    for c, ((e0, e1), n) in enumerate(pairs):
        router[c, e0], router[c, e1] = 1.0, 0.75
        h[at:at + n, c] = 4.0
        at += n
    w = dict(w, router=jnp.asarray(router),
             router_bias=jnp.zeros((8,), jnp.float32))
    return w, jnp.asarray(h)[None]


@pytest.mark.parametrize("first_expert,pairs,landed,busy", [
    (0, [((2, 3), 13)], 26, 2),                     # none at the start
    (0, [((0, 3), 21)], 42, 2),                     # none in the middle
    (0, [((0, 1), 9), ((1, 0), 2)], 22, 2),         # none at the end
    (0, [((2, 6), 33)], 33, 1),                     # one holds everything
    (0, [((4, 5), 19), ((7, 6), 6)], 0, 0),         # nothing lands here
    (0, [((0, 1), 8), ((2, 3), 16)], 48, 4),        # whole blocks of 8
    (4, [((5, 7), 5), ((4, 6), 12), ((1, 5), 7)], 41, 4),
    (4, [((0, 7), 16), ((3, 2), 8)], 16, 1),        # only the last, whole
], ids=["idle-start", "idle-middle", "idle-end", "one-holds-all",
        "none-lands", "whole-blocks", "first-expert-4", "last-alone"])
def test_the_share_is_the_references_wherever_the_work_lands(
        params, first_expert, pairs, landed, busy):
    """``_moe_share`` against the reference's loop over experts with the
    assignments steered so that held experts go without work at the start,
    in the middle and at the end of the buffer, one expert holds every
    assignment, sizes are whole blocks, the share starts at
    ``first_expert`` > 0, or nothing lands here at all: then the loop runs
    no block and the output is the shared expert's alone."""
    cfg = dataclasses.replace(CFG, moe=dataclasses.replace(
        CFG.moe, first_expert=first_expert))
    w, h = routed_to(layers_of(params)[0][1], pairs)
    got, stats = llama._moe_share(h, w, cfg, None)
    want = ref.experts(h[0], w, top_k=2, scale=1.0, held=(first_expert, 4))
    assert float(jnp.max(jnp.abs(got[0] - want))) < 1e-5
    T = h.shape[1]
    assert (int(stats[1]), int(stats[2]), int(stats[3])) == (
        landed, 2 * T - landed, busy)
    if landed == 0:
        np.testing.assert_array_equal(got, llama._shared_experts(h, w))
    else:
        assert float(jnp.max(jnp.abs(
            got - llama._shared_experts(h, w)))) > 1e-3


# -- the allocator: slots, snapshots, the trie ----------------------------------
def alloc(snapshots=2, pages=32):
    return PageAllocator(pages, PAGE, MAXP, state_slots=3,
                         state_snapshots=snapshots)


def run_to_end(a, sid, n):
    """Note the passes of a prefill of ``n`` tokens in one chunk to the
    snapshot boundary and one for the rest, as the engine does."""
    at = a.snapshot_boundary(sid, 0, n)
    if at:
        assert a.clamp_chunk(sid, 0, n, n) == at
        a.note_pass(sid, 0, at)
    a.note_pass(sid, at, n)


def test_a_match_longer_than_the_deepest_snapshot_is_cut_to_it():
    a = alloc()
    toks = list(range(100))
    s1 = a.allocate(70)
    assert a.page_table_row(s1).shape == (MAXP + 2,)
    assert a.page_table_row(s1)[-2] >= 0 and a.page_table_row(s1)[-1] >= 3
    run_to_end(a, s1, 70)           # snapshot at 64: (70 - 1) // 16 * 16
    a.free(s1, tokens=toks[:70])    # 4 full pages to the trie, snapshot on 4th
    assert a.snapshots_taken == 1
    pages, slot, full = a.match_prefix_state(toks[:99])
    assert len(pages) == 4 == full and slot >= 3
    # a second sequence runs on without reaching a further boundary it may
    # keep: its pages extend the chain, the snapshot stays at 64
    s2 = a.allocate(99, prefix_pages=pages)
    a.note_pass(s2, 64, 90)         # one pass, ends off a boundary
    a.free(s2, tokens=toks[:90])    # 5 full pages now
    assert len(a.match_prefix(toks[:99])) == 5
    pages, slot, full = a.match_prefix_state(toks[:99])
    assert (len(pages), full) == (4, 5) and slot >= 3
    assert a.state_slots_in_use() == (0, 1)


def test_a_snapshot_a_rolled_back_pass_overwrote_is_not_kept():
    a = alloc()
    toks = list(range(100))
    s = a.allocate(40)
    run_to_end(a, s, 40)            # snapshot at 32
    a.extend(s, 9)
    a.note_pass(s, 40, 49, each_token=True)    # passes reach 48: overwritten
    a.truncate(s, 45)               # ... but only 45 tokens were kept
    a.free(s, tokens=toks[:45])
    assert a.snapshots_taken == 0
    assert a.match_prefix_state(toks[:99]) == ([], -1, 2)


def test_evicting_a_snapshot_never_frees_pages_a_running_sequence_holds():
    a = alloc(snapshots=2)
    toks = list(range(200))
    s1 = a.allocate(40)
    run_to_end(a, s1, 40)
    a.free(s1, tokens=toks[:40])                # snapshot A at 32, on the trie
    pages, slot_a, _ = a.match_prefix_state(toks[:60])
    running = a.allocate(60, prefix_pages=pages)  # shares A's two pages
    held = a.pages_of(running)
    before = a.accounting()
    # two more sequences want snapshot slots: the pool has two and the
    # running sequence holds one, so the trie's snapshot A is evicted for
    # the first of them, and the second goes without
    other = [a.allocate(20) for _ in range(2)]
    assert a.snapshots_evicted == 1
    assert a.page_table_row(other[0])[-1] == slot_a
    assert a.page_table_row(other[1])[-1] == -1
    assert a.pages_of(running) == held
    after = a.accounting()
    assert after["trie"] == before["trie"] and after["total"] == before["total"]
    assert all(a._by_page[p].refcount == 1 for p in held[:2])
    # the chain is still there as pages, but no longer restorable
    assert len(a.match_prefix(toks[:60])) == 2
    assert a.match_prefix_state(toks[:60]) == ([], -1, 2)
    # a fourth sequence finds no live slot: admission queues
    from opsagent_tpu.serving.kvcache import OutOfPages
    with pytest.raises(OutOfPages, match="state slot"):
        a.allocate(10)
    for s in (running, *other):
        a.free(s)
    assert a.state_slots_in_use() == (0, 0)


def test_a_model_without_such_layers_allocates_nothing():
    a = PageAllocator(8, PAGE, MAXP)
    assert a.table_width == MAXP and a.state_slots == 0
    s = a.allocate(20)
    assert a.page_table_row(s).shape == (MAXP,)
    assert a.clamp_chunk(s, 0, 20, 20) == 20
    cache = llama.make_cache(PRESETS["tiny-test"], 8, PAGE)
    assert set(cache) == {"k", "v"}


# -- through the engine: admission, restore, decode blocks, finish -----------------
def test_two_turns_through_the_engine_restore_the_replys_snapshot():
    """A turn, then the history re-sent with more: greedy tokens are the
    argmax of ``forward_full`` both times, and the second admission restores
    the snapshot the first turn's decode left at its last page boundary
    (prompt 90 + 23 cached reply tokens -> 112) instead of prefilling it."""
    from opsagent_tpu import obs
    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    eng = Engine(EngineConfig(
        model="tiny-hybrid", dtype=jnp.float32, tp=1, max_batch_size=4,
        num_pages=128, max_pages_per_seq=32, prefill_buckets=(64,),
        mixed_buckets=(16, 32), max_step_tokens=64, decode_block=4,
        state_snapshots=3))
    rng = np.random.default_rng(0)
    sampling = SamplingParams(max_tokens=24, temperature=0.0)
    restored = "opsagent_state_restored_tokens_total"

    def turn(prompt):
        out = eng.generate([prompt], sampling)[0]
        full = llama.forward_full(
            eng.params, CFG, jnp.asarray([prompt + out]), dtype=jnp.float32)[0]
        want = [int(jnp.argmax(full[len(prompt) - 1 + i]))
                for i in range(len(out))]
        assert out == want
        return out

    first = [int(x) for x in rng.integers(0, 500, size=90)]
    reply = turn(first)
    before = obs.metrics_snapshot().get(restored, 0.0)
    turn(first + reply + [int(x) for x in rng.integers(0, 500, size=30)])
    assert obs.metrics_snapshot()[restored] - before == 112
    assert eng.alloc.state_slots_in_use()[0] == 0
    acc = eng.alloc.accounting()
    assert acc["free"] + acc["trie"] == acc["total"] and acc["owned"] == 0
    eng.sync_device_counters()
    share = {k: v for k, v in obs.metrics_snapshot().items()
             if k.startswith("opsagent_moe_share_total")}
    assert share['opsagent_moe_share_total{what="landed"}'] > 0
    assert share['opsagent_moe_share_total{what="absent"}'] > 0
    # the accumulators are uint32 and wrap: a delta is modulo 2**32
    passes = 'opsagent_moe_share_total{what="moe_layer_passes"}'
    before = obs.metrics_snapshot()[passes]
    assert eng.cache["stats"].dtype == jnp.uint32
    eng._moe_stats_seen = np.full(len(llama.MOE_STATS), 2**32 - 3, np.uint32)
    eng.cache = dict(eng.cache, stats=jnp.full_like(eng.cache["stats"], 2))
    eng.sync_device_counters()
    assert obs.metrics_snapshot()[passes] - before == 5


# -- the streaming attention kernel inside the hybrid stack (PR 29) ----------
def _solar_attention_widths():
    """The ``solar-open2-250b`` preset with its attention as published
    (64 query and 8 kv heads of 128, no rope, a gated output, one GQA
    layer a period of four) and everything attention never sees cut to
    what a CPU test holds: one period, a toy vocabulary and hidden size,
    4 of 8 experts, 4 linear heads."""
    full = PRESETS["solar-open2-250b"]
    return dataclasses.replace(
        full, name="solar-open2-attn", num_layers=4, vocab_size=512,
        hidden_size=128, intermediate_size=128, max_position=4096,
        moe=dataclasses.replace(
            full.moe, num_experts=4, router_experts=8,
            num_experts_per_token=2, expert_intermediate_size=32),
        linear_attn=dataclasses.replace(
            full.linear_attn, num_heads=4, key_head_dim=32,
            value_head_dim=32, gate_rank=16),
    )


def test_the_streaming_kernel_equals_the_gather_inside_the_hybrid_stack(
        monkeypatch):
    """A mixed step (a chunk row, a decode row, an idle row) and a decode
    step of the solar-open2 preset's attention, the kernel interpreted:
    logits, pages and recurrent state equal the gather path's at this
    file's tolerance. The kernel reads merged pages, the gather at 8 kv
    heads split ones, so the two caches hold the same bytes in two forms."""
    monkeypatch.setenv("OPSAGENT_PALLAS_INTERPRET", "1")
    cfg = _solar_attention_widths()
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_) == (64, 8, 128)
    p = _randomised(
        llama.init_params(cfg, jax.random.PRNGKey(2), jnp.float32),
        jax.random.PRNGKey(3))
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0, 512)
    table = table_rows([(range(8), 1, -1), (range(8, 16), 3, -1),
                        (range(16, 24), 5, -1)])
    first = np.zeros((3, 32), np.int32)
    first[0, :32] = np.asarray(toks[0, :32])
    first[1, :20] = np.asarray(toks[1, :20])
    second = np.zeros((3, 16), np.int32)
    second[0, 0] = int(toks[0, 32])
    second[1, :7] = np.asarray(toks[1, 20:27])
    got = {}
    for impl in ("xla", "pallas-stream"):
        form = llama.cache_form(cfg, 1, impl)
        assert form == ("merged" if impl == "pallas-stream" else "split")
        cache = llama.make_cache(
            cfg, 64, PAGE, dtype=jnp.float32, state_slots=8, form=form)
        _, cache = llama.mixed_step(
            p, cfg, jnp.asarray(first), jnp.zeros((3,), jnp.int32),
            jnp.asarray([32, 20, 0]), cache, table, dtype=jnp.float32,
            attn_impl=impl)
        mixed, cache = llama.mixed_step(
            p, cfg, jnp.asarray(second), jnp.asarray([32, 20, 0]),
            jnp.asarray([1, 7, 0]), cache, table, dtype=jnp.float32,
            attn_impl=impl)
        decoded, cache = llama.decode_step(
            p, cfg, jnp.asarray([int(toks[0, 33]), int(toks[1, 27]), 0]),
            jnp.asarray([33, 27, 0]), cache, table,
            jnp.asarray([True, True, False]), dtype=jnp.float32,
            attn_impl=impl)
        got[impl] = (mixed[:2], decoded[:2], cache["state"],
                     cache["k"].reshape(-1), cache["v"].reshape(-1))
    for a, b in zip(got["xla"], got["pallas-stream"]):
        assert float(jnp.max(jnp.abs(a - b))) < TOL


# -- Olmo-Hybrid: one decay a head, dk != dv, full-rank gates, post-norm (PR 33) -
#
# Against ``benchmarks/reference/olmo_hybrid.py``. The tolerance is wider
# than this file's other one, and why: the Olmo2 block norms a sublayer's
# OUTPUT, so where a mixer's output is small against the values it was
# computed from (a read-out ``S^T q`` whose terms cancel) the norm scales
# float32's rounding up with it. Measured here: the float32 reference
# against itself in float64 4.5e-4, the program against the float32
# reference 1.8e-3 at the worst of 200 positions and 3e-4 at most elsewhere
# (logits of magnitude 4.4). 6e-3 is three times the worst. A state held in
# bfloat16 between steps errs more than ten times that (asserted below).
from benchmarks.reference import olmo_hybrid as olmo_ref  # noqa: E402

OLMO = PRESETS["tiny-olmo-hybrid"]
OLMO_TOL = 6e-3


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
