"""Mixed prefill+decode batching (one weight stream per step).

Covers the ISSUE-2 acceptance gates on the tiny CPU engine: (a) greedy
token equivalence of the mixed path vs. the split prefill/decode path,
(b) the scheduler's token-budget policy (decode lanes funded first,
remainder to the oldest admitting prompts, honoring max_step_tokens),
(c) ZERO post-warmup XLA compiles across varied mixed-batch compositions
(the r04 sessions invariant, extended to the mixed programs), and
(d) prefix-cache hits still applying to chunks seated in mixed
dispatches.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opsagent_tpu.serving.engine import Engine, EngineConfig
from opsagent_tpu.serving.sampler import SamplingParams
from opsagent_tpu.serving.scheduler import Request, Scheduler

BASE = dict(
    model="tiny-test", dtype=jnp.float32, tp=1, page_size=4,
    num_pages=128, max_pages_per_seq=24, max_batch_size=4,
    prefill_buckets=(8, 16), decode_block=4,
    mixed_buckets=(4, 8, 16), max_step_tokens=32,
    # This file tests the SYNCHRONOUS mixed tick's contract (ISSUE-2);
    # the one-step-lookahead pipeline has its own acceptance suite in
    # tests/test_async_runtime.py.
    async_depth=1,
)

# Count real XLA compiles process-wide: the monitoring event fires once
# per backend compile and never on jit-cache hits. Registered once at
# import (jax.monitoring has no public deregistration); tests diff the
# counter around the window they care about.
_COMPILES: list[str] = []


def _on_event(name: str, *a, **kw) -> None:
    if name == "/jax/core/compile/backend_compile_duration":
        _COMPILES.append(name)


jax.monitoring.register_event_duration_secs_listener(_on_event)


def _drain_all(eng, sids):
    live = [s for s in sids if not eng.sequences[s].done]
    while live:
        eng.step_block(sorted(live))
        live = [s for s in live if not eng.sequences[s].done]
    eng.drain()


# 16 rows x a 16-slot bucket is 256 slots against a step of 128 tokens
# (Engine.step_tokens): the mixed program packs. BASE's 4 x 16 never does.
PACKED = dict(max_batch_size=16, num_pages=256, mixed_buckets=(16,))


@pytest.mark.parametrize(
    "over", [{}, PACKED, dict(PACKED, tp=2)],
    ids=["rows", "packed", "packed_tp2"])
def test_mixed_scheduler_matches_split_greedy(over):
    """(a) End-to-end through the scheduler: concurrent short + long
    prompts decoded under the mixed tick must be token-identical to the
    split-path oracle."""
    cfg = dict(BASE, **over)
    prompts = [
        [257, 9, 8, 7],
        [257] + list(range(1, 40)),     # multiple chunks
        [257, 5, 5, 5, 5, 5],
    ]
    budgets = [12, 6, 9]
    split = Engine(EngineConfig(mixed_batching=False, **cfg))
    want = [
        split.generate([p], SamplingParams(max_tokens=n))[0]
        for p, n in zip(prompts, budgets)
    ]

    eng = Engine(EngineConfig(mixed_batching=True, **cfg))
    assert eng.impl_info()["step_rows"] == (
        "packed:128" if over else "rows")
    sched = Scheduler(eng)
    sched.start()
    try:
        reqs = [
            sched.submit(Request(p, SamplingParams(max_tokens=n)))
            for p, n in zip(prompts, budgets)
        ]
        for r in reqs:
            assert r.done.wait(180)
            assert not r.error, r.error
        assert [r.tokens for r in reqs] == want
    finally:
        sched.stop()


def test_step_mixed_direct_matches_split_greedy():
    """(a) Engine-level: driving admission chunk-by-chunk through
    step_mixed while a decode lane rides along must reproduce both
    sequences' split-path generations exactly."""
    short = [257, 9, 8, 7]
    long_prompt = [257] + list(range(1, 40))
    split = Engine(EngineConfig(mixed_batching=False, **BASE))
    want_short = split.generate([short], SamplingParams(max_tokens=12))[0]
    want_long = split.generate([long_prompt], SamplingParams(max_tokens=6))[0]

    eng = Engine(EngineConfig(mixed_batching=True, **BASE))
    a = eng.add_request(short, SamplingParams(max_tokens=12))
    b = eng.begin_request(long_prompt, SamplingParams(max_tokens=6))
    collected = list(eng.sequences[a].tokens)
    mixed_dispatches = 0
    while b in eng._prefilling:
        done, total = eng.prefill_progress(b)
        dids = [a] if not eng.sequences[a].done else []
        d_out, p_out = eng.step_mixed(dids, {b: min(total - done, 16)})
        mixed_dispatches += 1
        collected.extend(d_out.get(a, []))
        assert not isinstance(p_out[b], Exception)
    assert mixed_dispatches >= 3          # 40 tokens through bucket-16 chunks
    _drain_all(eng, [a, b])
    collected.extend([])  # decode lane tokens already folded in
    while not eng.sequences[a].done:
        collected.extend(eng.step_block([a]).get(a, []))
    got_a, got_b = eng.finish(a), eng.finish(b)
    assert got_a == want_short
    assert got_b == want_long
    # The decode lane advanced DURING admission (mixed piggybacking).
    assert len(collected) > 1


def test_step_mixed_refuses_more_tokens_than_the_step_carries():
    """A direct caller that plans outside the scheduler's budget gets a
    ValueError before anything is booked, not a program nobody warmed:
    nine chunks of 16 are 144 tokens against a packed width of 128."""
    eng = Engine(EngineConfig(mixed_batching=True, **dict(BASE, **PACKED)))
    assert eng.step_tokens == 128
    prompt = [257] + list(range(1, 40))
    sids = [
        eng.begin_request(prompt, SamplingParams(max_tokens=4))
        for _ in range(9)
    ]
    owned = eng.alloc.accounting()["owned"]
    with pytest.raises(ValueError, match="exceeds the step's 128"):
        eng.step_mixed([], {sid: 16 for sid in sids})
    assert eng.alloc.accounting()["owned"] == owned
    assert all(eng.prefill_progress(sid)[0] == 0 for sid in sids)
    # eight of them are 128 tokens: the widest dispatch there is
    _, out = eng.step_mixed([], {sid: 16 for sid in sids[:8]})
    assert all(v is False for v in out.values())
    assert all(eng.prefill_progress(sid)[0] == 16 for sid in sids[:8])


def test_budget_policy_honors_max_step_tokens_and_decode_priority():
    """(b) Decode lanes are funded first; the admitting prompt gets
    exactly max_step_tokens - lanes (capped by the bucket ceiling), and a
    budget fully consumed by decode lanes yields no mixed dispatch."""
    cfg = dict(BASE, max_step_tokens=6, mixed_buckets=(4, 8, 16))
    eng = Engine(EngineConfig(**cfg))
    sched = Scheduler(eng)  # never started: ticks driven by hand
    short = [257, 1, 2, 3]
    long_prompt = [257] + list(range(1, 30))
    sched.submit(Request(short, SamplingParams(max_tokens=8)))
    sched._drain_queue()
    sched._try_admit()
    # Finish the short prompt's admission so it becomes a decode lane.
    while sched._prefilling:
        sched._advance_prefill()
    assert len(sched._running) == 1
    sched.submit(Request(long_prompt, SamplingParams(max_tokens=4)))
    sched._drain_queue()
    sched._try_admit()
    (bid,) = list(sched._prefilling)
    assert sched._mixed_tick() is True
    done, total = eng.prefill_progress(bid)
    # budget 6 - 1 decode lane = 5 chunk tokens, NOT the full bucket.
    assert done == 5
    # Starve the prefill budget entirely: lanes >= max_step_tokens.
    eng.cfg.max_step_tokens = 1
    assert sched._mixed_tick() is False   # falls back to the split tick
    eng.cfg.max_step_tokens = 64
    assert sched._mixed_tick() is True
    done2, _ = eng.prefill_progress(bid)
    assert done2 - done == min(16, total - done)  # bucket-capped chunk
    # Drain cleanly so the engine holds no half-admitted state.
    while sched._prefilling:
        if not sched._mixed_tick():
            sched._advance_prefill()
        sched._reap()
    for sid in list(sched._running):
        while not eng.sequences[sid].done:
            eng.step_block([sid])
        eng.drain()
    sched._reap()


def test_zero_compiles_after_warmup_across_mixed_compositions():
    """(c) The r04 invariant extended to mixed batching: after a
    sessions-level warmup, NO mixed-batch composition — varying decode
    lane counts, chunk sizes across every bucket, completing prompts,
    prefix-cache-backed chunks — may trigger an XLA compile."""
    cfg = EngineConfig(mixed_batching=True, **BASE)
    eng = Engine(cfg)
    eng.warmup("sessions")
    sampling = SamplingParams(max_tokens=6)

    n0 = len(_COMPILES)
    rng = np.random.default_rng(3)
    # Composition sweep: prompts sized to hit chunk buckets 4/8/16 with
    # 0..2 decode lanes riding along.
    sids: list[int] = []
    for plen in (3, 7, 13, 21, 37):
        prompt = [257] + [int(t) for t in rng.integers(1, 400, plen - 1)]
        b = eng.begin_request(prompt, sampling)
        while b in eng._prefilling:
            done, total = eng.prefill_progress(b)
            lanes = [s for s in sids if not eng.sequences[s].done][:2]
            eng.step_mixed(lanes, {b: min(total - done, 16)})
        sids.append(b)
    _drain_all(eng, sids)
    for s in sids:
        eng.finish(s)
    assert len(_COMPILES) == n0, (
        f"{len(_COMPILES) - n0} post-warmup compiles in mixed dispatches"
    )


def test_prefix_cache_hits_apply_to_mixed_chunks():
    """(d) A prompt sharing a cached prefix must start its mixed-path
    admission AT the matched offset (skipping the cached pages) and still
    generate exactly the uncached oracle's tokens."""
    base = [257] + list(range(1, 25))          # 24 tokens -> 6 full pages
    extended = base + [300, 301, 302, 303]
    split = Engine(EngineConfig(mixed_batching=False, **BASE))
    want = split.generate([extended], SamplingParams(max_tokens=6))[0]

    eng = Engine(EngineConfig(mixed_batching=True, **BASE))
    # Populate the trie: run the base prompt to completion and free it.
    a = eng.add_request(base, SamplingParams(max_tokens=4))
    _drain_all(eng, [a])
    eng.finish(a)

    hit0 = eng.alloc.hit_tokens
    b = eng.begin_request(extended, SamplingParams(max_tokens=6))
    assert eng.alloc.hit_tokens > hit0         # prefix matched at admission
    matched = eng._prefilling[b]
    assert matched > 0 and matched % eng.cfg.page_size == 0
    chunks = 0
    while b in eng._prefilling:
        done, total = eng.prefill_progress(b)
        assert done >= matched                 # never re-prefills the prefix
        eng.step_mixed([], {b: min(total - done, 16)})
        chunks += 1
    # The un-matched tail is < one bucket: exactly one mixed chunk.
    assert chunks == 1
    _drain_all(eng, [b])
    assert eng.finish(b) == want


def test_hosted_rows_fall_back_to_split_path():
    """A request needing host-side per-token work (logprobs) must route
    the tick to the split path — and still complete correctly alongside
    an admitting prompt under the mixed scheduler."""
    eng = Engine(EngineConfig(mixed_batching=True, **BASE))
    split = Engine(EngineConfig(mixed_batching=False, **BASE))
    p1 = [257, 3, 1, 4, 1, 5]
    p2 = [257] + list(range(1, 20))
    want1 = split.generate([p1], SamplingParams(max_tokens=5))[0]
    want2 = split.generate([p2], SamplingParams(max_tokens=5))[0]

    sched = Scheduler(eng)
    sched.start()
    try:
        r1 = sched.submit(Request(
            p1, SamplingParams(max_tokens=5, logprobs=True, top_logprobs=2)
        ))
        r2 = sched.submit(Request(p2, SamplingParams(max_tokens=5)))
        assert r1.done.wait(180) and r2.done.wait(180)
        assert not r1.error and not r2.error
        assert r1.tokens == want1
        assert r2.tokens == want2
        assert len(r1.logprob_data) == len(r1.tokens)
    finally:
        sched.stop()


def test_mixed_dispatch_composition_metrics_recorded():
    """The obs composition series (decode lanes, prefill tokens, budget
    utilization) must tick once per mixed dispatch."""
    from opsagent_tpu import obs

    snap0 = obs.metrics_snapshot()
    c0 = snap0.get("opsagent_mixed_dispatch_decode_lanes_count", 0)
    eng = Engine(EngineConfig(mixed_batching=True, **BASE))
    a = eng.add_request([257, 2, 3, 4], SamplingParams(max_tokens=8))
    b = eng.begin_request(
        [257] + list(range(1, 20)), SamplingParams(max_tokens=4)
    )
    n = 0
    while b in eng._prefilling:
        done, total = eng.prefill_progress(b)
        eng.step_mixed([a], {b: min(total - done, 16)})
        n += 1
    snap1 = obs.metrics_snapshot()
    assert snap1["opsagent_mixed_dispatch_decode_lanes_count"] == c0 + n
    assert snap1["opsagent_mixed_dispatch_prefill_tokens_sum"] >= 19 - 16
    assert (
        snap1['opsagent_decode_dispatches_total{kind="mixed"}']
        >= snap0.get('opsagent_decode_dispatches_total{kind="mixed"}', 0) + n
    )
    _drain_all(eng, [a, b])
    eng.finish(a), eng.finish(b)


def test_mixed_readers_byte_identical_and_int8_kv_compiles_nothing(
    stream_kernel
):
    """step_mixed across the two attention readers (the xla gather vs the
    streaming kernel, interpreted off-chip): chunked admission +
    interleaved decode lanes must produce byte-identical greedy output,
    and no mixed composition may compile post-warmup, under either reader
    nor with kv_quantize="int8", which only the gather reads."""
    prompts = [
        [257] + list(range(1, 12)),
        [257] + [5, 9, 2, 8, 1, 7, 3, 3, 4, 6, 2, 9, 8, 1, 5, 5, 2],
        [257, 4, 4, 2],
    ]

    def run(impl, **kw):
        eng = Engine(EngineConfig(mixed_batching=True, **kw, **BASE))
        assert eng.kernels.attn == impl
        eng.warmup("sessions")
        sampling = SamplingParams(max_tokens=8)
        n0 = len(_COMPILES)
        sids: list[int] = []
        for prompt in prompts:
            b = eng.begin_request(prompt, sampling)
            while b in eng._prefilling:
                done, total = eng.prefill_progress(b)
                lanes = [s for s in sids if not eng.sequences[s].done][:2]
                eng.step_mixed(lanes, {b: min(total - done, 16)})
            sids.append(b)
        live = [s for s in sids if not eng.sequences[s].done]
        while live:
            eng.step_mixed(live, {})
            live = [s for s in live if not eng.sequences[s].done]
        assert len(_COMPILES) == n0, (
            f"{len(_COMPILES) - n0} post-warmup compiles on {impl} {kw}"
        )
        return [eng.finish(s) for s in sids]

    want = run("xla")
    with stream_kernel():
        assert run("pallas-stream") == want
        run("xla", kv_quantize="int8")      # the kernel has no int8 reader


# -- the packed mixed step (llama.Pack): tokens, not slots ---------------------
@pytest.mark.parametrize("preset,over", [
    ("tiny-test", {"attn_bias": True}),     # dense GQA with QKV bias
    ("tiny-mla", {}),                       # latent pages: the gather path
], ids=["gqa_qkv_bias", "mla_latent"])
def test_packed_mixed_step_is_the_rows_step(
        packed_against_rows, ragged_case, preset, over):
    """The residual stream packed to the tick's tokens gives the logits and
    the cache of the stream laid out rows x bucket, at every ragged shape:
    the same float32 mathematics, the matmuls' rows in another order."""
    import dataclasses

    from opsagent_tpu.models import llama
    from opsagent_tpu.models.config import PRESETS

    cfg = dataclasses.replace(PRESETS[preset], **over)
    params = llama.init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    if over:
        # init_params leaves the biases at zero; a dropped one must show
        params["layers"] = {
            name: jax.random.normal(jax.random.PRNGKey(9), leaf.shape) * 0.1
            if name in ("bq", "bk", "bv") else leaf
            for name, leaf in params["layers"].items()}
    q_lens, S = ragged_case
    with jax.default_matmul_precision("highest"):
        packed_against_rows(cfg, params, q_lens, S, tol=2e-5)


# -- the packed step's page write: by token, to the bit ------------------------
_WRITE_MODELS = {
    "gqa_qkv_bias_rope": {"attn_bias": True},
    "mha_no_rope": {"num_kv_heads": 4, "use_rope": False},
    "qk_norm_by_head": {"qk_norm": True},
    "qk_norm_whole": {"qk_norm": True, "qk_norm_whole": True},
}
_WRITE_CACHES = {      # make_cache's form and kv_quantize
    "split": ("split", ""), "merged": ("merged", ""),
    "int8_split": ("split", "int8"), "int8_merged": ("merged", "int8"),
}
_SENTINEL = 7


@pytest.fixture(scope="module")
def written_caches():
    """``caches(model, cache, q_lens, S, start, table)``: the cache trees
    two packed ``llama.mixed_step`` programs leave (32 tokens; dense
    segments 16 wide in a tick of 16 or fewer), one that writes by rows as
    the step did before PR 39 (q, k AND v un-packed to ``[B, S]`` rows
    ahead of the heads' split and RoPE, the rows' ``B x S`` slots handed to
    ``write_kv_pages``) and the one the tree has, from pages that hold a
    sentinel in every slot (values, int8 values and scales alike), as
    numpy leaves; float32. Both run the projections over the same packed
    tokens, so the bits can be compared (over ``[B, S]`` rows a CPU matmul
    rounds another way: ``test_packed_mixed_step_is_the_rows_step`` holds
    that pair within a tolerance). Six rows, pages of 4."""
    import dataclasses
    import functools

    from opsagent_tpu.models import llama
    from opsagent_tpu.models.config import PRESETS

    B, T, PAGE = 6, 32, 4

    @functools.lru_cache(maxsize=None)
    def model(name):
        cfg = dataclasses.replace(PRESETS["tiny-test"], **_WRITE_MODELS[name])
        params = llama.init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
        # biases and norm gains off their zeros and ones: a dropped one shows
        params["layers"] = {
            leaf_name: leaf + jax.random.normal(
                jax.random.PRNGKey(9), leaf.shape) * 0.1
            if leaf_name in ("bq", "bk", "bv", "qn", "kn") else leaf
            for leaf_name, leaf in params["layers"].items()}
        return cfg, params

    def by_rows(params, *, cfg, tokens, start, q_lens, cache, page_table):
        def qkv_rope(x, lp, cfg, cos, sin, pack, tok_rope):
            q, k, v = pack.dense(lambda a: llama._qkv_flat(a, lp, cfg), x)
            q, k, v = llama._heads(
                pack.rows(q), pack.rows(k), pack.rows(v), lp, cfg)
            if cos is None:
                return q, k, v
            return (llama.apply_rope(q, cos, sin) * llama._yarn_q_scale(cfg),
                    llama.apply_rope(k, cos, sin), v)

        def write(kc, vc, k, v, slots, layer):
            return llama.write_kv_pages(
                kc, vc, k, v, page_table, start, valid_len=q_lens,
                layer=layer)

        with pytest.MonkeyPatch.context() as mp:    # traced once, patched
            mp.setattr(llama, "_qkv_rope", qkv_rope)
            mp.setattr(llama, "write_kv_tokens", write)
            return llama.mixed_step(
                params, cfg, tokens, start, q_lens, cache, page_table,
                dtype=jnp.float32, step_tokens=T)

    @functools.lru_cache(maxsize=None)
    def step(cfg, rows):
        return jax.jit(functools.partial(
            by_rows if rows else llama.mixed_step, cfg=cfg,
            **({} if rows else {"dtype": jnp.float32, "step_tokens": T})))

    def caches(name, cache_form, q_lens, S, start, table):
        cfg, params = model(name)
        form, quant = _WRITE_CACHES[cache_form]
        tokens = jnp.asarray(np.random.default_rng(S).integers(
            1, cfg.vocab_size, (B, S)), jnp.int32)
        out = []
        with jax.default_matmul_precision("highest"):
            for rows in (True, False):
                cache = jax.tree.map(
                    lambda a: jnp.full_like(a, _SENTINEL),
                    llama.make_cache(
                        cfg, int(np.max(table)) + 2, PAGE, dtype=jnp.float32,
                        kv_quantize=quant, form=form))
                _, cache = step(cfg, rows)(
                    params, tokens=tokens,
                    start=jnp.asarray(start, jnp.int32),
                    q_lens=jnp.asarray(q_lens, jnp.int32), cache=cache,
                    page_table=jnp.asarray(table, jnp.int32))
                out.append([np.asarray(a) for a in jax.tree.leaves(cache)])
        return cfg, out

    return caches


def _slots_written(leaf, pages: int, page: int = 4):
    """[pages, page] which slots of layer 0 no longer hold the sentinel."""
    per_slot = np.asarray(leaf[0]).reshape(pages, page, -1)
    return (per_slot != _SENTINEL).any(axis=-1)


@pytest.mark.parametrize("cache_form", list(_WRITE_CACHES))
@pytest.mark.parametrize("name", list(_WRITE_MODELS))
def test_packed_step_writes_the_rows_steps_cache_to_the_bit(
        written_caches, ragged_case, name, cache_form):
    """Keys and values that never leave the packed stream (projection,
    heads, qk-norm, RoPE at the tokens' own positions, one scatter of ``T``
    tokens: ``ops.attention.write_kv_tokens``) land in the slots the rows
    program writes with the bits it writes, in every page slot of every
    layer, and no other slot changes: GQA with QKV bias and RoPE, MHA
    without RoPE, a qk-norm a head and one over the whole projection;
    split and merged pages; int8 pages' values and scales."""
    q_lens, S = ragged_case
    table = np.arange(6 * 16, dtype=np.int32).reshape(6, 16)
    _, (rows, packed) = written_caches(
        name, cache_form, q_lens, S, [5, 0, 0, 9, 20, 2], table)
    assert len(rows) == (4 if "int8" in cache_form else 2)
    for a, b in zip(rows, packed, strict=True):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    # what was written is each row's q_len slots from its start, no more
    written = _slots_written(packed[0], 6 * 16 + 1).reshape(-1)
    want = np.zeros_like(written)
    for b, (st, n) in enumerate(zip([5, 0, 0, 9, 20, 2], q_lens)):
        want[b * 64 + st: b * 64 + st + n] = True
    assert np.array_equal(written, want)


@pytest.mark.parametrize("cache_form", ["merged", "int8_split"])
def test_packed_write_drops_what_has_no_slot_and_crosses_pages(
        written_caches, cache_form):
    """One tick through the packed write: a row with ``q_len`` 0 and the
    tokens past the tick's last write nothing; a row whose second page is
    unassigned (-1) keeps the tokens of its first page and drops the
    rest; a chunk that starts two slots before a page's end lands on both
    of its pages (pages that are not neighbours). As the rows program."""
    table = np.full((6, 16), -1, np.int32)
    table[0, :4] = [9, 2, 30, 4]       # chunk over pages 9 -> 2 -> 30
    table[1, :1] = [11]                # second page unassigned
    table[2, :2] = [5, 6]              # q_len 0: nothing
    table[3, :3] = [20, 21, 22]        # a decode row
    q_lens, start = (7, 6, 0, 1, 0, 0), [2, 1, 3, 8, 0, 0]
    _, (rows, packed) = written_caches(
        "gqa_qkv_bias_rope", cache_form, q_lens, 16, start, table)
    for a, b in zip(rows, packed, strict=True):
        assert np.array_equal(a, b)
    written = _slots_written(packed[0], 32)
    want = np.zeros_like(written)
    want[9, 2:] = want[2, :] = want[30, :1] = True      # 2 + 4 + 1 tokens
    want[11, 1:] = True                 # 3 of 6: the others had no page
    want[22, 0] = True                  # position 8: third page, slot 0
    assert np.array_equal(written, want)


@pytest.mark.parametrize("args,rc", [(["--rehearse"], 3), ([], 1)],
                         ids=["rehearse", "refuses_the_cpu"])
def test_kv_write_microbench_walks_here_and_times_only_on_the_chip(
        tmp_path, args, rc):
    """``scripts/kv_write_microbench.py`` (PERF.md section 6, PR 39: what a
    row handed to the page write's scatter costs): ``--rehearse`` walks
    every form at toy shapes on the CPU, checks each form's cache against
    numpy's, prints no time and exits 3; without it the script refuses a
    CPU and prints no line."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, os.path.join(root, "scripts/kv_write_microbench.py"),
         *args],
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == rc, done.stderr[-2000:]
    lines = [json.loads(x) for x in done.stdout.splitlines()
             if x.startswith("{")]
    if not args:
        assert lines == [] and "needs a TPU" in done.stdout
        return
    assert len(lines) == 4 and {x["rows"] for x in lines} == {16, 32}
    for line in lines:
        assert line["real"] <= line["rows"]
        assert set(line["us_per_call"]) == {
            "scatter", "scatter_unique", "write_pages", "write_kv_tokens"}
        assert all(v is None for v in line["us_per_call"].values())
    kept = (tmp_path / "chiprun_out/kv_write_microbench.jsonl").read_text()
    assert [json.loads(x) for x in kept.splitlines()] == lines
