"""Engine tests: greedy generation vs the full-forward oracle, batching
equivalence, page lifecycle, constrained masks, streaming."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opsagent_tpu.models import llama
from opsagent_tpu.models.config import TINY_TEST
from opsagent_tpu.serving.engine import Engine, EngineConfig
from opsagent_tpu.serving.kvcache import OutOfPages
from opsagent_tpu.serving.sampler import SamplingParams


@pytest.fixture(scope="module")
def engine():
    cfg = EngineConfig(
        model="tiny-test",
        dtype=jnp.float32,
        tp=1,
        page_size=4,
        num_pages=64,
        max_pages_per_seq=16,
        max_batch_size=4,
        prefill_buckets=(16, 32),
        seed=0,
    )
    return Engine(cfg)


def ref_greedy(engine, prompt, n):
    """Teacher-forced oracle: full causal forward + argmax each step."""
    toks = list(prompt)
    out = []
    for _ in range(n):
        logits = llama.forward_full(
            engine.params, engine.model_cfg, jnp.asarray([toks]), dtype=jnp.float32
        )
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
        if nxt == engine.tokenizer.eos_id:
            break
    return out


def test_generate_matches_oracle(engine):
    prompt = [257, 72, 101, 108, 108, 111]
    want = ref_greedy(engine, prompt, 8)
    got = engine.generate([prompt], SamplingParams(max_tokens=8))[0]
    assert got[: len(want)] == want


def test_batch_matches_individual(engine):
    p1 = [257, 10, 20, 30]
    p2 = [257, 99, 98, 97, 96, 95, 94]
    want1 = engine.generate([p1], SamplingParams(max_tokens=6))[0]
    want2 = engine.generate([p2], SamplingParams(max_tokens=6))[0]
    got = engine.generate([p1, p2], SamplingParams(max_tokens=6))
    assert got[0] == want1
    assert got[1] == want2


def test_long_generation_crosses_pages(engine):
    # page_size=4: 20 tokens forces several page extensions mid-decode.
    prompt = [257, 1, 2, 3, 4, 5, 6, 7, 8, 9]  # 10 tokens = 3 pages
    want = ref_greedy(engine, prompt, 14)
    got = engine.generate([prompt], SamplingParams(max_tokens=14))[0]
    assert got[: len(want)] == want


def test_pages_freed_after_finish(engine):
    # First pass may DONATE full pages to the prefix trie (finish()
    # retains them as evictable cache, not leaked) — so the conservation
    # check runs on the steady state: an identical second generate must
    # return the allocator to exactly the first pass's level, and the
    # donated prefix must be re-borrowed, not re-allocated.
    prompt = [257, 1, 2, 3, 4, 5]
    engine.generate([prompt], SamplingParams(max_tokens=5))
    free_after_first = engine.alloc.free_pages
    engine.generate([prompt], SamplingParams(max_tokens=5))
    assert engine.alloc.free_pages == free_after_first
    assert engine.sequences == {}


def test_out_of_pages():
    cfg = EngineConfig(
        model="tiny-test", dtype=jnp.float32, tp=1,
        page_size=4, num_pages=2, max_pages_per_seq=2,
        max_batch_size=2, prefill_buckets=(16,),
    )
    small = Engine(cfg)
    sid = small.add_request([257, 1, 2, 3, 4, 5], SamplingParams(max_tokens=2))
    with pytest.raises(OutOfPages):
        small.add_request([257, 1, 2, 3, 4, 5], SamplingParams(max_tokens=2))
    small.finish(sid)
    # After freeing, admission succeeds again.
    sid2 = small.add_request([257, 9, 8, 7], SamplingParams(max_tokens=2))
    small.finish(sid2)


def test_constrained_mask_forbids_tokens(engine):
    prompt = [257, 42, 43, 44]
    free = ref_greedy(engine, prompt, 1)[0]

    def mask_fn(generated):
        m = np.ones((engine.model_cfg.vocab_size,), bool)
        m[free] = False  # forbid exactly the greedy choice
        return m

    sid = engine.add_request(prompt, SamplingParams(max_tokens=1), mask_fn=mask_fn)
    got = engine.finish(sid)
    assert got[0] != free


def test_stream_callback(engine):
    seen = []
    sid = engine.add_request(
        [257, 5, 6, 7], SamplingParams(max_tokens=4), stream=seen.append
    )
    while not engine.sequences[sid].done:
        engine.step([sid])
    toks = engine.finish(sid)
    assert seen == toks


def test_ttft_recorded(engine):
    sid = engine.add_request([257, 1], SamplingParams(max_tokens=1))
    seq_ttft = engine.sequences[sid].ttft_s
    engine.finish(sid)
    assert seq_ttft > 0


# -- block decode (decode_loop.decode_block via Engine.step_block) ----------
def test_step_block_matches_single_steps(engine):
    """The multi-step device loop must produce exactly the single-step
    greedy tokens (same programs, one dispatch)."""
    prompt = [257, 11, 22, 33, 44]
    sid1 = engine.add_request(prompt, SamplingParams(max_tokens=10))
    while not engine.sequences[sid1].done:
        engine.step([sid1])
    want = engine.finish(sid1)

    sid2 = engine.add_request(prompt, SamplingParams(max_tokens=10))
    while not engine.sequences[sid2].done:
        engine.step_block([sid2])
    got = engine.finish(sid2)
    assert got == want


def test_step_block_respects_max_tokens(engine):
    # max_tokens smaller than the block: the device budget must stop the row.
    prompt = [257, 3, 1, 4, 1, 5]
    sid = engine.add_request(prompt, SamplingParams(max_tokens=3))
    while not engine.sequences[sid].done:
        engine.step_block([sid])
    got = engine.finish(sid)
    assert len(got) == 3


def test_step_block_stop_string_rolls_back(engine):
    """A stop string hit mid-block truncates the accepted tokens and rolls
    the page accounting back; no pages may leak.

    The stop string is derived from the reference generation by scanning
    for the first token whose decoded text has not appeared earlier in the
    decoded output (the old hard-coded ``ref[1]`` assumed greedy tokens
    never repeat — weight-dependent, and false for the current seed, whose
    generation opens with a run of identical bytes)."""
    owned_before = engine.alloc.accounting()["owned"]
    prompt = [257, 11, 22, 33, 44]
    ref = ref_greedy(engine, prompt, 10)
    stop_txt = want_len = None
    for j in range(1, len(ref)):
        s = engine.tokenizer.decode([ref[j]])
        # Need a clean single-token text that first appears at step j:
        # replacement chars ("�", partial multi-byte sequences) also
        # render for OTHER incomplete tokens, so they cannot anchor a
        # first-occurrence scan.
        if not s or "�" in s:
            continue
        if s in engine.tokenizer.decode(ref[:j]):
            continue
        stop_txt, want_len = s, j + 1
        break
    assert stop_txt is not None, f"no usable stop token in {ref}"
    sid = engine.add_request(
        prompt, SamplingParams(max_tokens=10, stop=(stop_txt,))
    )
    while not engine.sequences[sid].done:
        engine.step_block([sid])
    seq = engine.sequences[sid]
    assert seq.finish_reason == "stop"
    got = engine.finish(sid)
    # The token matching the stop string ends generation.
    assert len(got) == want_len
    # No leak: every page is free, trie-donated (evictable), or owned by
    # someone else; this sequence holds nothing. (The old free_pages
    # equality only held when the donation was a single page — a donated
    # CHAIN's interior nodes are evictable-after-their-children, which
    # free_pages deliberately does not count.)
    acc = engine.alloc.accounting()
    assert acc["total"] == engine.cfg.num_pages
    assert acc["owned"] == owned_before


def test_step_block_batch_with_mixed_finishes(engine):
    p1 = [257, 10, 20, 30]
    p2 = [257, 99, 98, 97, 96, 95, 94]
    want1 = engine.generate([p1], SamplingParams(max_tokens=2))[0]
    want2 = engine.generate([p2], SamplingParams(max_tokens=9))[0]
    s1 = engine.add_request(p1, SamplingParams(max_tokens=2))
    s2 = engine.add_request(p2, SamplingParams(max_tokens=9))
    while not (engine.sequences[s1].done and engine.sequences[s2].done):
        engine.step_block([s1, s2])
    assert engine.finish(s1) == want1
    assert engine.finish(s2) == want2


def test_extend_upto_and_truncate_invariants():
    from opsagent_tpu.serving.kvcache import PageAllocator

    a = PageAllocator(num_pages=8, page_size=4, max_pages_per_seq=4)
    sid = a.allocate(6)           # 2 pages
    assert a.free_pages == 6
    got = a.extend_upto(sid, 16)  # wants 4 more pages, cap allows 2 more
    assert got == 10              # 2 slack in page 2 + 2 fresh pages
    assert a.length(sid) == 16
    assert a.free_pages == 4
    a.truncate(sid, 7)
    assert a.length(sid) == 7
    assert a.free_pages == 6      # back to 2 pages held
    a.free(sid)
    assert a.free_pages == 8


def test_step_block_mixed_masked_and_plain(engine):
    """A constrained row must not stop unconstrained rows from
    block-decoding, and both must advance correctly together."""
    prompt_m = [257, 42, 43, 44]
    prompt_p = [257, 11, 22, 33, 44]
    want_p = engine.generate([prompt_p], SamplingParams(max_tokens=8))[0]
    free = ref_greedy(engine, prompt_m, 1)[0]

    def mask_fn(generated):
        m = np.ones((engine.model_cfg.vocab_size,), bool)
        m[free] = False
        return m

    sm = engine.add_request(
        prompt_m, SamplingParams(max_tokens=4), mask_fn=mask_fn
    )
    sp = engine.add_request(prompt_p, SamplingParams(max_tokens=8))
    while not (engine.sequences[sm].done and engine.sequences[sp].done):
        out = engine.step_block([sm, sp])
        if sp in out and not engine.sequences[sp].done:
            assert len(out[sp]) >= 1
    got_m = engine.finish(sm)
    got_p = engine.finish(sp)
    assert got_p == want_p
    assert got_m[0] != free


def test_step_block_raising_stream_rolls_back_pages(engine):
    """A stream callback raising mid-block must still roll page accounting
    back to the accepted tokens (prefix-cache poisoning guard)."""
    free_before = engine.alloc.free_pages

    calls = []

    def boom(tok):
        calls.append(tok)
        if len(calls) == 3:
            raise RuntimeError("client went away")

    sid = engine.add_request(
        [257, 5, 6, 7], SamplingParams(max_tokens=12), stream=boom
    )
    with pytest.raises(RuntimeError, match="client went away"):
        while not engine.sequences[sid].done:
            engine.step_block([sid])
    seq = engine.sequences[sid]
    assert seq.done and seq.finish_reason == "error"
    # allocator length must equal the accepted token count invariant
    assert engine.alloc.length(sid) == seq.prompt_len + len(seq.tokens) - 1
    engine.finish(sid)
    assert engine.alloc.free_pages == free_before


def test_step_block_seq_ids_filter_only_advances_requested(engine):
    """With both sequences lane-seated, step_block([a]) must not advance b
    (its lane keeps the device carry but gets no budget)."""
    a = engine.add_request([257, 1, 2, 3], SamplingParams(max_tokens=12))
    b = engine.add_request([257, 4, 5, 6], SamplingParams(max_tokens=12))
    engine.step_block([a, b])  # seat both lanes
    engine.drain()             # settle the seating dispatch's tokens
    n_b = len(engine.sequences[b].tokens)
    for _ in range(6):
        if engine.sequences[a].done:
            break
        engine.step_block([a])
    engine.drain()
    assert len(engine.sequences[b].tokens) == n_b
    # b still advances fine afterwards.
    while not (engine.sequences[a].done and engine.sequences[b].done):
        engine.step_block([a, b])
    engine.finish(a)
    engine.finish(b)


def test_drain_merges_multi_block_pulls(engine):
    """drain() pulling several in-flight blocks for the same sequence must
    concatenate their tokens, not keep only the last block's."""
    want = engine.generate([[257, 8, 9]], SamplingParams(max_tokens=40))[0]
    sid = engine.add_request([257, 8, 9], SamplingParams(max_tokens=40))
    collected = list(engine.sequences[sid].tokens)  # admission's first token
    # Fill the pipeline without pulling everything, then drain.
    for _ in range(4):
        out = engine.step_block([sid])
        collected.extend(out.get(sid, []))
    collected.extend(engine.drain().get(sid, []))
    while not engine.sequences[sid].done:
        out = engine.step_block([sid])
        collected.extend(out.get(sid, []))
    collected.extend(engine.drain().get(sid, []))
    got = engine.finish(sid)
    assert got == want
    assert collected == want


def test_warmup_compiles_without_disturbing_state():
    """warmup() must leave page accounting and generation untouched: a
    warmed engine produces exactly what an unwarmed one does, and no pages
    leak (warmup writes through all-dropped page tables)."""
    cfg = EngineConfig(
        model="tiny-test", dtype=jnp.float32, tp=1, page_size=4,
        num_pages=32, max_pages_per_seq=8, max_batch_size=2,
        prefill_buckets=(16, 32),
    )
    cold = Engine(cfg)
    want = cold.generate([[257, 1, 2, 3]], SamplingParams(max_tokens=5))[0]

    warm = Engine(cfg)
    free_before = warm.alloc.free_pages
    dt = warm.warmup()
    assert dt > 0
    assert warm.alloc.free_pages == free_before
    assert warm.sequences == {}
    got = warm.generate([[257, 1, 2, 3]], SamplingParams(max_tokens=5))[0]
    assert got == want


@pytest.mark.parametrize(
    "progs,breaks",
    [
        ({"mixed"}, "program"),          # the sequential dispatch pass
        ({"mixed_async", "fsm"}, "fsm"),  # async mixed + ToolPrompt tables
        ({"ffwd"}, "fsm"),                # grammar fast-forward family
        ({"fsm"}, "fsm"),                 # device-FSM decode blocks
    ],
    ids=["mixed", "mixed_async-fsm", "ffwd", "fsm"],
)
def test_failing_warmup_family_raises(monkeypatch, progs, breaks):
    """A warmup family that cannot be built is fatal: on the chip it is a
    program the compiler refuses, and start-up must not exit clean only
    to compile — or crash — inside the first request. (Each of these
    four used to log "non-fatal" and carry on.)"""
    eng = Engine(EngineConfig(
        model="tiny-test", dtype=jnp.float32, tp=1, page_size=4,
        num_pages=64, max_pages_per_seq=8, max_batch_size=2,
        prefill_buckets=(8,), mixed_buckets=(4,), decode_block=4,
    ))
    monkeypatch.setitem(Engine.WARMUP_LEVELS, "only", frozenset(progs))

    class Refused(RuntimeError):
        pass

    def refuse(*a, **kw):
        raise Refused("Mosaic failed to compile TPU kernel")

    if breaks == "fsm":
        monkeypatch.setattr(eng, "_toolprompt_fsm_tables", refuse)
    else:
        class Program:
            lower = __call__ = staticmethod(refuse)

        monkeypatch.setattr(eng, "_mixed_sample_jit", Program())
    with pytest.raises(Refused):
        eng.warmup("only")


def test_sessions_free_warmup_builds_no_grammar_table(monkeypatch):
    """"sessions-free" is "sessions" less the grammar families: under
    free-text traffic no FSM table exists, so none is built and no FSM
    variant of a step program is compiled."""
    levels = Engine.WARMUP_LEVELS
    assert levels["sessions-free"] == levels["sessions"] - {"fsm", "ffwd"}
    eng = Engine(EngineConfig(
        model="tiny-test", dtype=jnp.float32, tp=1, page_size=4,
        num_pages=64, max_pages_per_seq=8, max_batch_size=2,
        prefill_buckets=(8,), mixed_buckets=(4,), decode_block=4,
    ))

    def refuse(*a, **kw):
        raise AssertionError("a grammar table was asked for")

    monkeypatch.setattr(eng, "_toolprompt_fsm_tables", refuse)
    assert eng.warmup("sessions-free") > 0


def test_compilation_cache_lives_in_one_place(tmp_path, monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set the cache lives there;
    where it is not, at one fixed git-ignored path inside the checkout
    (never a temporary name, pid or time). No call ever points JAX at
    any other directory."""
    import os

    from opsagent_tpu.serving import engine as engine_mod
    from opsagent_tpu.serving.engine import (
        compile_cache_dir, enable_compilation_cache,
    )

    pointed: list[str] = []
    real_update = jax.config.update

    def spy(name, value):
        if name == "jax_compilation_cache_dir":
            pointed.append(value)
        return real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    path = enable_compilation_cache()
    assert path == str(tmp_path / "xla") and os.path.isdir(path)
    assert jax.config.jax_compilation_cache_dir == path
    # Already there (as when the environment set it before jax was
    # imported): a second call sets nothing.
    n = len(pointed)
    assert enable_compilation_cache() == path and len(pointed) == n

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = enable_compilation_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert fixed == compile_cache_dir() == enable_compilation_cache()
    assert os.path.dirname(fixed) == os.path.join(repo, ".jax_cache")
    assert os.path.basename(fixed).startswith(jax.default_backend())
    assert jax.config.jax_compilation_cache_dir == fixed
    assert set(pointed) == {str(tmp_path / "xla"), fixed}
    # The directory is git-ignored, and an engine uses the same one.
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    assert engine_mod._CHECKOUT == repo


def test_prefill_chunks_interleave_with_decode():
    """VERDICT item 5: admitting a long prompt must not stall running
    decodes. begin_request/prefill_step split admission into bucket-sized
    chunks; a running stream advances between chunks."""
    cfg = EngineConfig(
        model="tiny-test", dtype=jnp.float32, tp=1, page_size=4,
        num_pages=64, max_pages_per_seq=16, max_batch_size=4,
        prefill_buckets=(8, 16), decode_block=4,
        prefix_cache=False,  # the oracle runs below would otherwise donate
                             # the long prompt's pages and skip its chunks
    )
    eng = Engine(cfg)
    # Oracle outputs via isolated synchronous runs.
    short = [257, 5, 6, 7]
    long_prompt = [257] + list(range(1, 40))   # 40 tokens = 3 chunks of <=16
    want_short = eng.generate([short], SamplingParams(max_tokens=12))[0]
    want_long = eng.generate([long_prompt], SamplingParams(max_tokens=4))[0]

    a = eng.add_request(short, SamplingParams(max_tokens=12))
    b = eng.begin_request(long_prompt, SamplingParams(max_tokens=4))
    assert not eng.sequences[b].tokens  # prefilling, not decodable yet

    chunks = 0
    decoded_between = 0
    while True:
        finished = eng.prefill_step(b)
        chunks += 1
        if finished:
            break
        if not eng.sequences[a].done:
            out = eng.step_block([a])
            decoded_between += sum(len(v) for v in out.values())
    assert chunks == 3               # 16 + 16 + 8
    eng.drain()
    # The running stream made progress while the long prompt admitted.
    assert decoded_between + len(eng.sequences[a].tokens) > 1
    while not (eng.sequences[a].done and eng.sequences[b].done):
        eng.step_block([a, b])
    assert eng.finish(a) == want_short
    assert eng.finish(b) == want_long


def test_scheduler_long_admission_keeps_decodes_flowing():
    """Scheduler-level: a long prompt admitting one chunk per tick must not
    block a concurrently running stream; both complete correctly."""
    from opsagent_tpu.serving.scheduler import Request, Scheduler

    cfg = EngineConfig(
        model="tiny-test", dtype=jnp.float32, tp=1, page_size=4,
        num_pages=96, max_pages_per_seq=24, max_batch_size=4,
        prefill_buckets=(8, 16), decode_block=4,
    )
    eng = Engine(cfg)
    short = [257, 9, 8, 7]
    long_prompt = [257] + list(range(1, 60))   # 60 tokens = 4 chunks
    want_short = eng.generate([short], SamplingParams(max_tokens=16))[0]
    want_long = eng.generate([long_prompt], SamplingParams(max_tokens=4))[0]

    sched = Scheduler(eng)
    sched.start()
    try:
        r1 = sched.submit(Request(short, SamplingParams(max_tokens=16)))
        r2 = sched.submit(Request(long_prompt, SamplingParams(max_tokens=4)))
        assert r1.done.wait(120) and r2.done.wait(120)
        assert not r1.error and not r2.error
        assert r1.tokens == want_short
        assert r2.tokens == want_long
    finally:
        sched.stop()


def test_batched_prefill_matches_sequential():
    """engine.prefill_batch (one dispatch for several admitting sequences,
    mixed fresh/partial states as prefix rows) must produce the same first
    tokens and generations as chunk-at-a-time prefill_step admission."""
    import jax.numpy as jnp

    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    kw = dict(
        model="tiny-test", dtype=jnp.float32, tp=1, page_size=8,
        num_pages=256, max_pages_per_seq=32, max_batch_size=4,
        prefill_buckets=(8, 16),
    )
    prompts = [
        list(range(1, 13)),          # 12 tokens: chunks under bucket 16
        [7, 7, 8, 9],                # short fresh prompt
        list(range(20, 44)),         # 24 tokens: multiple chunks
    ]
    sampling = SamplingParams(temperature=0.0, max_tokens=6)

    want_eng = Engine(EngineConfig(**kw))
    want = want_eng.generate(prompts, sampling)

    eng = Engine(EngineConfig(**kw))
    sids = [eng.begin_request(p, sampling) for p in prompts]
    pending = set(sids)
    while pending:
        # Group is the caller's job; batch everything sharing the first
        # sequence's bucket, chunk the rest alone.
        first = sorted(pending)[0]
        bucket = eng.next_prefill_bucket(first)
        batch = [
            s for s in sorted(pending)
            if eng.next_prefill_bucket(s) == bucket
        ][: eng.cfg.prefill_batch]
        res = eng.prefill_batch(batch)
        pending -= {s for s, done in res.items() if done is True}
    live = {s for s in sids if not eng.sequences[s].done}
    while live:
        eng.step_block(sorted(live))
        live = {s for s in live if not eng.sequences[s].done}
    got = [eng.finish(s) for s in sids]
    assert got == want, (got, want)


def test_batched_prefill_isolates_bad_row():
    """A raising stream callback in one batched admission must fail ONLY
    that row: the other sequences keep their pages and first tokens."""
    import jax.numpy as jnp

    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    eng = Engine(EngineConfig(
        model="tiny-test", dtype=jnp.float32, tp=1, page_size=8,
        num_pages=256, max_pages_per_seq=32, max_batch_size=4,
        prefill_buckets=(16,),
    ))
    free0 = eng.alloc.free_pages
    sampling = SamplingParams(temperature=0.0, max_tokens=4)

    def boom(_tok):
        raise RuntimeError("client went away")

    good = eng.begin_request([1, 2, 3, 4], sampling)
    bad = eng.begin_request([5, 6, 7], sampling, stream=boom)
    res = eng.prefill_batch([good, bad])
    assert res[good] is True
    assert isinstance(res[bad], RuntimeError)
    assert bad not in eng.sequences  # cleaned up
    assert len(eng.sequences[good].tokens) == 1  # first token sampled
    # Page accounting: only the good sequence holds pages now.
    while not eng.sequences[good].done:
        eng.step_block([good])
    eng.finish(good)
    assert eng.alloc.free_pages == free0


# -- which reader of paged keys and values an engine runs ---------------------
QWEN_7B = dict(head_dim=128, kv_heads_per_shard=4, page_itemsize=2)
# MLA that holds the latent, as its reader is handed it: ONE head of
# ``page_dim`` lanes, keys and values alike (GLM-4.7-Flash: 576 -> 640)
GLM_LATENT = dict(head_dim=640, kv_heads_per_shard=1, page_itemsize=2,
                  mla=True, shared_kv=True)


@pytest.mark.parametrize("platform,shapes,backend", [
    ("tpu", QWEN_7B, "pallas-stream"),                       # cell 1
    ("tpu", dict(QWEN_7B, kv_heads_per_shard=8), "pallas-stream"),  # 2 and 3
    ("tpu", dict(QWEN_7B, kv_heads_per_shard=1), "pallas-stream"),  # 7B, tp=4
    ("tpu", dict(QWEN_7B, page_itemsize=1), "xla"),          # int8 pages
    ("tpu", dict(QWEN_7B, head_dim=64), "xla"),              # off the lanes
    ("tpu", dict(QWEN_7B, head_dim=192, mla=True), "xla"),   # MLA's qk heads
    ("tpu", GLM_LATENT, "pallas-stream"),                    # cell 5
    ("tpu", dict(GLM_LATENT, head_dim=576), "xla"),          # the row unpadded
    ("tpu", dict(GLM_LATENT, page_itemsize=1), "xla"),       # int8 latent
    ("tpu", dict(GLM_LATENT, tp=4), "xla"),                  # one head, 4 shards
    ("tpu", dict(GLM_LATENT, shared_kv=False, head_dim=256), "xla"),
    ("cpu", GLM_LATENT, "xla"),
    ("cpu", QWEN_7B, "xla"),                                 # the tests' oracle
    ("gpu", QWEN_7B, "xla"),
])
def test_the_attention_backend_is_chosen_from_platform_and_shapes(
    platform, shapes, backend
):
    """No knob: the choice resolves from what the engine can observe
    where it is built. The streaming kernel on a TPU wherever the chip's
    compiler takes it, the gather everywhere else."""
    from opsagent_tpu.ops.kernels import (
        paged_attention_backend, pallas_refusal,
    )

    assert paged_attention_backend(platform=platform, **shapes) == backend
    if platform == "tpu":
        refused = pallas_refusal("pallas-stream", **shapes)
        assert (refused is None) == (backend == "pallas-stream")


def test_the_choice_is_a_pure_function_whatever_the_environment_names(
    monkeypatch
):
    """The variable that once named a backend outright is read by nothing:
    set to a reader, to a kernel that is gone or to nonsense, the choice
    is what it is without it, and nothing is raised."""
    from opsagent_tpu.ops.kernels import (
        PAGED_BACKENDS, paged_attention_backend,
    )

    assert PAGED_BACKENDS == ("xla", "pallas-stream")
    int8 = dict(QWEN_7B, page_itemsize=1)
    for named in ("xla", "pallas-stream", "pallas-dma", "auto", "cuda"):
        monkeypatch.setenv("OPSAGENT_PAGED_BACKEND", named)
        assert paged_attention_backend(platform="tpu", **QWEN_7B) == (
            "pallas-stream")
        assert paged_attention_backend(platform="tpu", **int8) == "xla"
        assert paged_attention_backend(platform="cpu", **QWEN_7B) == "xla"


def test_nothing_in_the_package_reads_a_variable_that_names_a_reader():
    """A grep over the package: no ``OPSAGENT_*BACKEND`` variable and no
    ``OPSAGENT_ATTN*`` / ``OPSAGENT_PAGED*`` one, which also guards the
    next knob. (``OPSAGENT_PALLAS_INTERPRET`` says how a ``pallas_call`` is
    lowered, not which reader runs, and is an error on the chip.)"""
    import pathlib
    import re

    import opsagent_tpu

    knob = re.compile(r"OPSAGENT_\w*(BACKEND|ATTN|ATTENTION|PAGED)\w*")
    found = [
        f"{path}: {m.group(0)}"
        for path in pathlib.Path(opsagent_tpu.__file__).parent.rglob("*.py")
        for m in knob.finditer(path.read_text())
    ]
    assert found == []


def test_an_engine_on_the_cpu_runs_the_gather(engine):
    """The default on this platform, and what ``impl_info`` says of it.
    (The two page counters this test also read, made for the gather over
    the padded capacity, went with PR 38: nothing read them.)"""
    info = engine.impl_info()
    assert (info["platform"], info["attn_impl"]) == ("cpu", "xla")
    out = engine.generate(
        [[257, 5, 6, 7, 8, 9]], SamplingParams(max_tokens=6))
    assert len(out[0]) == 6


def _gather_and_kernel(stream_kernel, run, model_cfg=None, **cfg):
    """``run(engine)`` on the gather's engine and on the kernel's (the
    conftest fixture: the choice patched, the kernel interpreted), and
    what the kernel's engine says of itself."""
    want = run(Engine(EngineConfig(**cfg), model_cfg=model_cfg))
    with stream_kernel():
        eng = Engine(EngineConfig(**cfg), model_cfg=model_cfg)
        return want, run(eng), eng.impl_info()


SMALL = dict(
    dtype=jnp.float32, max_batch_size=2, num_pages=16, max_pages_per_seq=8,
    prefill_buckets=(32,), mixed_buckets=(16,), mixed_batching=True,
)
PROMPTS = [[257] + list(range(1, 20)), [257, 4, 4, 2]]


def test_a_model_with_recurrent_state_takes_the_streaming_kernel(stream_kernel):
    """``_row_state`` strips the state-slot columns before attention sees
    the table and the family's GQA layers are plain GQA to this op, so
    the engine no longer refuses such a model an attention backend; the
    kernel (interpreted here) serves what the gather serves."""
    want, got, info = _gather_and_kernel(
        stream_kernel,
        lambda eng: eng.generate(PROMPTS, SamplingParams(max_tokens=6)),
        model="tiny-hybrid", tp=1, **SMALL,
    )
    assert info["attn_impl"] == "pallas-stream"
    assert got == want


def test_the_streaming_kernel_serves_one_kv_head_a_shard_under_tp(stream_kernel):
    """tiny-test's two kv heads over tp=2 leave ONE a shard: the pages
    are held split with the heads' axis sharded, and the dispatch's form
    check counts the heads a shard holds, not the array's (the 7B over
    tp=4). The kernel, interpreted, serves what the gather serves."""
    want, got, info = _gather_and_kernel(
        stream_kernel,
        lambda eng: eng.generate(PROMPTS, SamplingParams(max_tokens=6)),
        model="tiny-test", tp=2, **SMALL,
    )
    assert (info["attn_impl"], info["kv_page_form"]) == (
        "pallas-stream", "split")
    assert got == want


# Each program that reaches attention by a door of its own, on tiny-test
# (two kv heads: merged pages under the kernel), pages of 4 slots.
DOORS = dict(
    model="tiny-test", dtype=jnp.float32, tp=1, page_size=4, num_pages=64,
    max_pages_per_seq=16, max_batch_size=4, prefill_buckets=(8, 16),
    decode_block=4, mixed_buckets=(4, 8, 16), max_step_tokens=32, seed=0,
)
LONG = [257] + list(range(1, 40))
SHORT = [257, 9, 8, 7]


def _run_mixed_async(eng):
    """``step_mixed_async`` at depth 2: a decode lane rides while a longer
    prompt is admitted in chunks, then both decode to their end."""
    a = eng.add_request(SHORT, SamplingParams(max_tokens=10))
    b = eng.begin_request(LONG, SamplingParams(max_tokens=6))
    ticks = 0
    while not (eng.sequences[a].done and eng.sequences[b].done):
        chunks = {}
        if b in eng._prefilling:
            done, total = eng.prefill_progress(b)
            if total > done:
                chunks = {b: min(total - done, 16)}
        lanes = [
            s for s in (a, b)
            if s not in eng._prefilling and not eng.sequences[s].done
        ]
        eng.step_mixed_async(lanes, chunks)
        ticks += 1
        assert ticks < 200, "async driving made no progress"
    eng.async_drain()
    return [eng.finish(a), eng.finish(b)]


def _run_blocks(eng):
    """Split prefill (``prefill_step``: the prompt is longer than the
    largest bucket, so its tail attends over pages), then fused decode
    blocks (``step_block``, the decode form: what cell 2 runs between
    admissions)."""
    return eng.generate([LONG, SHORT], SamplingParams(max_tokens=9))


def _run_single_steps(eng):
    """The single decode ``step``, a token a dispatch."""
    sid = eng.add_request(SHORT, SamplingParams(max_tokens=7))
    while not eng.sequences[sid].done:
        eng.step([sid])
    return eng.finish(sid)


def _run_cached_prefix(eng):
    """Admission over a cached prefix: the second prompt shares the
    first's 24 tokens, the trie hands their pages over, and
    ``prefill_step`` computes only the tail against them."""
    sp = SamplingParams(max_tokens=5)
    first = eng.generate([LONG[:28]], sp)
    before = eng.alloc.hit_tokens
    second = eng.generate([LONG[:24] + [7, 7, 3, 1, 2]], sp)
    assert eng.alloc.hit_tokens - before >= 16
    return first + second


@pytest.mark.parametrize("run,cfg", [
    pytest.param(_run_mixed_async, dict(async_depth=2), id="mixed-async"),
    pytest.param(_run_blocks, {}, id="blocks"),
    pytest.param(_run_single_steps, {}, id="single-steps"),
    pytest.param(_run_cached_prefix, {}, id="cached-prefix"),
])
def test_the_kernels_engine_generates_what_the_gathers_does(
    stream_kernel, run, cfg
):
    want, got, _ = _gather_and_kernel(stream_kernel, run, **DOORS, **cfg)
    assert got == want and all(got)


def test_the_streaming_kernel_serves_merged_pages_sharded_by_lanes(
    stream_kernel
):
    """Four kv heads over tp=2 leave two a shard: held merged, the ``K*D``
    axis sharded over tp, each shard's kernel reading its own heads'
    lanes (the 72B's eight heads over tp=4)."""
    import dataclasses

    want, got, info = _gather_and_kernel(
        stream_kernel,
        lambda eng: eng.generate(PROMPTS, SamplingParams(max_tokens=6)),
        model_cfg=dataclasses.replace(TINY_TEST, num_heads=4, num_kv_heads=4),
        model="tiny-test", tp=2, **SMALL,
    )
    assert (info["attn_impl"], info["kv_page_form"]) == (
        "pallas-stream", "merged")
    assert got == want


def test_impl_info_names_the_kernel_and_the_form_it_reads(stream_kernel):
    """Under the fixture every engine with a reader says ``pallas-stream``
    with the form ``page_form`` gives it: merged at tiny-test's two kv
    heads, split (a unit axis) at one head a shard, merged for MLA's
    latent (one head, no unit axis); without a reader (int8 pages, MLA's
    materialised heads) it says ``xla`` with the gather's form."""
    rows = [
        (dict(model="tiny-test", tp=1), "pallas-stream", "merged"),
        (dict(model="tiny-test", tp=2), "pallas-stream", "split"),
        (dict(model="tiny-test", tp=1, kv_quantize="int8"), "xla", "merged"),
        (dict(model="tiny-mla", tp=1), "xla", None),
        (dict(model="tiny-glm-flash", tp=1), "pallas-stream", "merged"),
    ]
    with stream_kernel():
        for cfg, impl, form in rows:
            eng = Engine(EngineConfig(**cfg, **SMALL))
            info = eng.impl_info()
            assert info["attn_impl"] == impl, cfg
            assert info["kv_page_form"] == llama.cache_form(
                eng.model_cfg, cfg["tp"], impl), cfg
            assert form in (None, info["kv_page_form"]), cfg


@pytest.mark.parametrize("cfg,words", [
    (dict(model="tiny-test", kv_quantize="int8"), "int8 pages"),
    (dict(model="tiny-test"), "head_dim 16"),
    (dict(model="tiny-mla"), "MLA"),
], ids=["int8-pages", "head-dim-off-the-lanes", "mla"])
def test_what_the_kernel_cannot_read_on_a_tpu_goes_to_the_gather_and_the_log_says_why(
    monkeypatch, cfg, words
):
    """An engine whose choice is asked as on a TPU, at each of the three
    things the kernel has no reader for: it runs the gather, and the line
    that names the reader carries the refusal's reason."""
    import logging

    from opsagent_tpu.ops import kernels

    choice = kernels.paged_attention_backend
    asked, lines = [], []
    # The program's loggers do not propagate to the root that caplog reads.
    handler = logging.Handler(logging.INFO)
    handler.emit = lambda record: lines.append(record.getMessage())
    logger = logging.getLogger("opsagent.engine")

    def on_a_tpu(*, platform, **shapes):
        asked.append(shapes)
        return choice(platform="tpu", **shapes)

    monkeypatch.setattr(kernels, "paged_attention_backend", on_a_tpu)
    logger.addHandler(handler)
    try:
        eng = Engine(EngineConfig(**cfg, **dict(SMALL, dtype=jnp.bfloat16)))
    finally:
        logger.removeHandler(handler)
    assert eng.impl_info()["attn_impl"] == "xla"
    why = kernels.pallas_refusal("pallas-stream", **asked[0])
    assert words in why
    line = next(x for x in lines if "paged attention reader" in x)
    assert "reader: xla" in line and why in line
