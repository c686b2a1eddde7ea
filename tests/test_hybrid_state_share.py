"""A model of unlike layers against the plain reference (tests/
hybrid_state_common.py has the model, the reference and the tolerances): the
expert share at one chip's width, the allocator's state slots and snapshots
under the prefix trie, two turns through the engine, and the streaming
attention kernel inside the hybrid stack.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import solar_open2 as ref
from opsagent_tpu.models import llama
from opsagent_tpu.models.config import PRESETS
from opsagent_tpu.ops.kernels import Kernels
from opsagent_tpu.serving.kvcache import PageAllocator
from hybrid_state_common import (  # noqa: F401 (fixtures)
    CFG,
    MAXP,
    PAGE,
    TOL,
    _randomised,
    highest,
    layers_of,
    params,
    release_compiled_programs,
    table_rows,
)


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
            kernels=Kernels(attn=impl))
        mixed, cache = llama.mixed_step(
            p, cfg, jnp.asarray(second), jnp.asarray([32, 20, 0]),
            jnp.asarray([1, 7, 0]), cache, table, dtype=jnp.float32,
            kernels=Kernels(attn=impl))
        decoded, cache = llama.decode_step(
            p, cfg, jnp.asarray([int(toks[0, 33]), int(toks[1, 27]), 0]),
            jnp.asarray([33, 27, 0]), cache, table,
            jnp.asarray([True, True, False]), dtype=jnp.float32,
            kernels=Kernels(attn=impl))
        got[impl] = (mixed[:2], decoded[:2], cache["state"],
                     cache["k"].reshape(-1), cache["v"].reshape(-1))
    for a, b in zip(got["xla"], got["pallas-stream"]):
        assert float(jnp.max(jnp.abs(a - b))) < TOL
