"""Grammar-accelerated decoding: forced-token fast-forward (ISSUE-13).

When a constrained row's FSM state admits exactly ONE token, that token
needs no forward pass — whole runs of forced tokens splice into the
paged KV as one multi-token append (the q_len>1 path the mixed program
already serves) and only the token AFTER the run is sampled. Gates
covered on the tiny CPU engine:

- byte-identical greedy output with the fast-forward on vs off, through
  the sync tick AND the async depth-2 pipeline, on fp32 AND int8 KV
  pages;
- a forced run crossing max_tokens is truncated (never overshoots) and
  a stop string landing mid-run finishes the row exactly like the
  oracle, with page bookings rolled back;
- exact page conservation around multi-token appends;
- ZERO post-warmup XLA compiles from the fast-forward dispatch shapes;
- the fast-lane smoke: skipped forward passes actually reported
  (opsagent_ffwd_* counters move) while text stays byte-identical;
- hosted-mask rows counted under the ffwd_ineligible fallback reason.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opsagent_tpu import obs
from opsagent_tpu.serving.constrained import (
    TOOLPROMPT_SCHEMA,
    json_constraint,
)
from opsagent_tpu.serving.engine import Engine, EngineConfig
from opsagent_tpu.serving.sampler import SamplingParams
from opsagent_tpu.serving.scheduler import Request, Scheduler

BASE = dict(
    model="tiny-test", dtype=jnp.float32, tp=1, page_size=4,
    num_pages=128, max_pages_per_seq=24, max_batch_size=4,
    prefill_buckets=(8, 16), decode_block=4,
    mixed_buckets=(4, 8, 16), max_step_tokens=32,
)

PROMPT = [257, 3, 1, 4]

# Count real XLA compiles process-wide (the test_mixed_batching pattern):
# the monitoring event fires once per backend compile, never on jit-cache
# hits; tests diff the counter around the window they care about.
_COMPILES: list[str] = []


def _on_event(name: str, *a, **kw) -> None:
    if name == "/jax/core/compile/backend_compile_duration":
        _COMPILES.append(name)


jax.monitoring.register_event_duration_secs_listener(_on_event)


def _metric(name: str) -> float:
    return float(obs.metrics_snapshot().get(name, 0.0))


def _oracle(sampling=None, **cfg_over):
    """Constrained greedy generation with the fast-forward OFF."""
    kw = {**BASE, **cfg_over}
    eng = Engine(EngineConfig(grammar_ffwd=False, async_depth=1, **kw))
    con = json_constraint(eng.tokenizer, TOOLPROMPT_SCHEMA)
    sid = eng.begin_request(
        PROMPT, sampling or SamplingParams(max_tokens=24), mask_fn=con
    )
    while not eng.prefill_step(sid):
        pass
    while not eng.sequences[sid].done:
        eng.step_block([sid])
    reason = eng.sequences[sid].finish_reason
    return eng.finish(sid), reason


def _drive_sync_ffwd(eng, sid, check_pages=False):
    """Alternate the fast-forward entry point with block decode. The
    drain settles the block pipeline between blocks, which is what lets
    the pre-scan see current host token lists (the scheduler gets the
    same chance at admission/settle boundaries)."""
    n = 0
    while not eng.sequences[sid].done:
        eng.drain()
        if check_pages:
            s = eng.sequences[sid]
            # Settled: every booked slot is written content (+1 for the
            # next sampled token is booked per-dispatch, not held).
            assert eng.alloc.length(sid) == eng._host_written(s)
        out = eng.ffwd_step([sid])
        if not out.get(sid) and not eng.sequences[sid].done:
            eng.step_block([sid])
        n += 1
        assert n < 300, "ffwd driving made no progress"
    eng.drain()


def _drive_async(eng, sid):
    n = 0
    while not eng.sequences[sid].done:
        eng.step_mixed_async([sid], {})
        n += 1
        assert n < 300, "async driving made no progress"
    eng.drain()


def test_sync_ffwd_smoke_byte_identical_and_skips():
    """The fast-lane CPU smoke: fast-forward ON must skip a nonzero
    number of forward passes (counters move) while the generated bytes
    stay identical to the oracle's."""
    want, _ = _oracle()
    eng = Engine(EngineConfig(grammar_ffwd=True, async_depth=1, **BASE))
    con = json_constraint(eng.tokenizer, TOOLPROMPT_SCHEMA)
    k0 = _metric("opsagent_ffwd_skipped_dispatches_total")
    t0 = _metric("opsagent_ffwd_tokens_total")
    r0 = _metric("opsagent_ffwd_runs_total")
    sid = eng.begin_request(
        PROMPT, SamplingParams(max_tokens=24), mask_fn=con
    )
    while not eng.prefill_step(sid):
        pass
    _drive_sync_ffwd(eng, sid)
    got = eng.finish(sid)
    assert got == want, (got, want)
    assert _metric("opsagent_ffwd_skipped_dispatches_total") > k0
    assert _metric("opsagent_ffwd_tokens_total") > t0
    assert _metric("opsagent_ffwd_runs_total") > r0
    # Skipped dispatches == forced tokens for the single-row sync drive.
    assert (
        _metric("opsagent_ffwd_skipped_dispatches_total") - k0
        == _metric("opsagent_ffwd_tokens_total") - t0
    )


def test_async_depth2_ffwd_byte_identical_and_skips():
    """The lookahead pipeline plans forced runs at every dispatch point:
    identical bytes, nonzero skips, no drive tricks needed."""
    want, _ = _oracle()
    eng = Engine(EngineConfig(grammar_ffwd=True, async_depth=2, **BASE))
    con = json_constraint(eng.tokenizer, TOOLPROMPT_SCHEMA)
    k0 = _metric("opsagent_ffwd_skipped_dispatches_total")
    sid = eng.begin_request(
        PROMPT, SamplingParams(max_tokens=24), mask_fn=con
    )
    while not eng.prefill_step(sid):
        pass
    _drive_async(eng, sid)
    got = eng.finish(sid)
    assert got == want, (got, want)
    assert _metric("opsagent_ffwd_skipped_dispatches_total") > k0


def test_int8_kv_pages_ffwd_byte_identical():
    """Quantized KV pages go through the same q_len>1 append path: the
    fast-forward must stay byte-identical on int8 pages too (sync and
    async forms)."""
    want, _ = _oracle(kv_quantize="int8")
    for depth, drive in ((1, _drive_sync_ffwd), (2, _drive_async)):
        eng = Engine(EngineConfig(
            grammar_ffwd=True, async_depth=depth, kv_quantize="int8",
            **BASE,
        ))
        con = json_constraint(eng.tokenizer, TOOLPROMPT_SCHEMA)
        k0 = _metric("opsagent_ffwd_skipped_dispatches_total")
        sid = eng.begin_request(
            PROMPT, SamplingParams(max_tokens=24), mask_fn=con
        )
        while not eng.prefill_step(sid):
            pass
        drive(eng, sid)
        got = eng.finish(sid)
        assert got == want, (depth, got, want)
        assert _metric("opsagent_ffwd_skipped_dispatches_total") > k0


def test_scheduler_equivalence_both_depths():
    """End-to-end through the scheduler at depth 1 and 2, fast-forward
    on: a constrained row next to a plain multi-chunk prompt must match
    the fast-forward-off oracle token-for-token."""
    p_plain = [257] + list(range(1, 20))

    def run(depth, ffwd):
        eng = Engine(EngineConfig(
            async_depth=depth, grammar_ffwd=ffwd, **BASE
        ))
        sched = Scheduler(eng)
        sched.start()
        try:
            rc = sched.submit(Request(
                PROMPT, SamplingParams(max_tokens=24),
                mask_fn=json_constraint(eng.tokenizer, TOOLPROMPT_SCHEMA),
            ))
            rp = sched.submit(Request(p_plain, SamplingParams(max_tokens=8)))
            assert rc.done.wait(180) and rp.done.wait(180)
            assert not rc.error and not rp.error, (rc.error, rp.error)
        finally:
            sched.stop()
        return rc.tokens, rp.tokens

    want = run(1, False)
    assert run(1, True) == want
    assert run(2, True) == want


def test_forced_run_crossing_max_tokens_truncated():
    """A forced run that would carry the row past max_tokens is clamped
    BEFORE dispatch: the row finishes as 'length' at exactly max_tokens,
    token-identical to the oracle — never an overshoot to discard."""
    for cap in (6, 11):
        sampling = SamplingParams(max_tokens=cap)
        want, reason = _oracle(sampling=sampling)
        assert reason == "length" and len(want) == cap
        for depth, drive in ((1, _drive_sync_ffwd), (2, _drive_async)):
            eng = Engine(EngineConfig(
                grammar_ffwd=True, async_depth=depth, **BASE
            ))
            con = json_constraint(eng.tokenizer, TOOLPROMPT_SCHEMA)
            sid = eng.begin_request(PROMPT, sampling, mask_fn=con)
            while not eng.prefill_step(sid):
                pass
            drive(eng, sid)
            assert eng.sequences[sid].finish_reason == "length"
            got = eng.finish(sid)
            assert got == want, (depth, cap, got, want)
            assert len(got) == cap


def test_stop_string_mid_forced_run():
    """A stop string completing INSIDE a forced run ('ques' lands inside
    the forced key name 'question') must finish the row exactly where
    the oracle does, roll the dead tail of the booked append back, and
    leave no pages owned."""
    sampling = SamplingParams(max_tokens=24, stop=("ques",))
    want, reason = _oracle(sampling=sampling)
    assert reason == "stop"
    free_want, _ = _oracle()
    assert len(want) < len(free_want)  # the stop actually bites
    for depth, drive in ((1, _drive_sync_ffwd), (2, _drive_async)):
        eng = Engine(EngineConfig(
            grammar_ffwd=True, async_depth=depth, **BASE
        ))
        con = json_constraint(eng.tokenizer, TOOLPROMPT_SCHEMA)
        acc0 = eng.alloc.accounting()
        sid = eng.begin_request(PROMPT, sampling, mask_fn=con)
        while not eng.prefill_step(sid):
            pass
        drive(eng, sid)
        assert eng.sequences[sid].finish_reason == "stop"
        got = eng.finish(sid)
        assert got == want, (depth, got, want)
        acc1 = eng.alloc.accounting()
        assert acc1["total"] == acc0["total"] == BASE["num_pages"]
        assert acc1["owned"] == 0, acc1


def test_page_conservation_around_multi_token_appends():
    """Allocator accounting through the sync fast-forward drive: at every
    settled point booked slots == written content exactly (the append
    booked 1+len(run) and accepted all of it), and after finish no pages
    stay owned."""
    eng = Engine(EngineConfig(grammar_ffwd=True, async_depth=1, **BASE))
    con = json_constraint(eng.tokenizer, TOOLPROMPT_SCHEMA)
    acc0 = eng.alloc.accounting()
    k0 = _metric("opsagent_ffwd_skipped_dispatches_total")
    sid = eng.begin_request(
        PROMPT, SamplingParams(max_tokens=24), mask_fn=con
    )
    while not eng.prefill_step(sid):
        pass
    _drive_sync_ffwd(eng, sid, check_pages=True)
    assert _metric("opsagent_ffwd_skipped_dispatches_total") > k0
    eng.finish(sid)
    acc1 = eng.alloc.accounting()
    assert acc1["total"] == acc0["total"] == BASE["num_pages"]
    assert acc1["owned"] == 0, acc1
    assert acc1["free"] + acc1["trie"] == acc1["total"]


@pytest.mark.parametrize("depth", [1, 2], ids=["sync", "async"])
def test_forced_runs_are_trimmed_to_the_steps_width(depth):
    """Sixteen constrained rows reach the schema's forced key names in
    the same tick: 16 x (1 + up to 15) tokens against a step of 128
    (``Engine.step_tokens``; the 16-slot bucket's program packs to it). The
    planner cuts the runs so that no dispatch carries more, the cut rows
    catch up in later steps, every row serves the tokens of the
    fast-forward-off oracle, nothing compiles after warm-up whichever
    bucket a tick lands in, and no page stays booked."""
    over = dict(max_batch_size=16, num_pages=256, mixed_buckets=(8, 16))
    sampling = SamplingParams(max_tokens=40)
    want, _ = _oracle(sampling, **over)
    eng = Engine(EngineConfig(
        grammar_ffwd=True, async_depth=depth, **dict(BASE, **over)))
    assert eng.step_tokens == 128
    eng.warmup("full")
    carried: list[int] = []
    count = eng._count_step_tokens
    eng._count_step_tokens = lambda S, real: (
        carried.append(real), count(S, real))
    sids = []
    for _ in range(16):
        sid = eng.begin_request(
            PROMPT, sampling,
            mask_fn=json_constraint(eng.tokenizer, TOOLPROMPT_SCHEMA))
        while not eng.prefill_step(sid):
            pass
        sids.append(sid)
    n0 = len(_COMPILES)
    t0 = _metric("opsagent_ffwd_tokens_total")
    for _ in range(300):
        live = [s for s in sids if not eng.sequences[s].done]
        if not live:
            break
        if depth > 1:
            eng.step_mixed_async(live, {})
            continue
        eng.drain()
        out = eng.ffwd_step(live)
        rest = [s for s in live
                if not out.get(s) and not eng.sequences[s].done]
        if rest:
            eng.step_block(rest)
    eng.drain()
    assert max(carried) == eng.step_tokens, carried   # the cut engaged
    assert _metric("opsagent_ffwd_tokens_total") - t0 > 16 * 8
    assert len(_COMPILES) == n0
    assert [eng.finish(s) for s in sids] == [want] * 16
    acc = eng.alloc.accounting()
    assert acc["owned"] == 0 and acc["free"] + acc["trie"] == acc["total"]


def test_zero_compiles_after_warmup_with_ffwd():
    """The r04 invariant extended to the fast-forward append shapes:
    after warmup (which compiles the multi-token append buckets), a
    constrained run that fast-forwards — sync AND async — must trigger
    no XLA compile."""
    for depth, drive in ((1, _drive_sync_ffwd), (2, _drive_async)):
        eng = Engine(EngineConfig(
            grammar_ffwd=True, async_depth=depth, **BASE
        ))
        eng.warmup("full")
        con = json_constraint(eng.tokenizer, TOOLPROMPT_SCHEMA)
        n0 = len(_COMPILES)
        k0 = _metric("opsagent_ffwd_skipped_dispatches_total")
        sid = eng.begin_request(
            PROMPT, SamplingParams(max_tokens=24), mask_fn=con
        )
        while not eng.prefill_step(sid):
            pass
        drive(eng, sid)
        eng.finish(sid)
        assert _metric("opsagent_ffwd_skipped_dispatches_total") > k0
        assert len(_COMPILES) == n0, (
            f"{len(_COMPILES) - n0} post-warmup compiles in depth-{depth} "
            "ffwd dispatches"
        )


def test_hosted_mask_rows_counted_ffwd_ineligible():
    """A plain-callable mask (no dense device tables) can never
    fast-forward: the row must be counted once under the dedicated
    fallback reason and still match the unconstrained oracle (the mask
    allows everything)."""
    eng = Engine(EngineConfig(grammar_ffwd=True, async_depth=2, **BASE))
    sync = Engine(EngineConfig(grammar_ffwd=False, async_depth=1, **BASE))
    want = sync.generate([PROMPT], SamplingParams(max_tokens=6))[0]

    def mask_all(generated):
        return np.ones((eng.model_cfg.vocab_size,), bool)

    f0 = _metric('opsagent_async_fallbacks_total{reason="ffwd_ineligible"}')
    sched = Scheduler(eng)
    sched.start()
    try:
        r = sched.submit(Request(
            PROMPT, SamplingParams(max_tokens=6), mask_fn=mask_all
        ))
        assert r.done.wait(180)
        assert not r.error, r.error
        assert r.tokens == want
    finally:
        sched.stop()
    assert _metric(
        'opsagent_async_fallbacks_total{reason="ffwd_ineligible"}'
    ) > f0


def test_ffwd_readers_byte_identical_and_int8_kv_compiles_nothing(
    stream_kernel
):
    """The grammar fast-forward's q_len>1 forced-run appends ride the
    streaming kernel too: async depth-2 under the kernel (interpreted
    off-chip) must produce byte-identical constrained greedy output vs
    the xla gather, with ZERO post-warmup compiles under the warmed
    mixed/ffwd groups, under either reader and with int8 KV pages, which
    only the gather reads."""
    def run(impl, **kw):
        eng = Engine(EngineConfig(
            grammar_ffwd=True, async_depth=2, **kw, **BASE
        ))
        assert eng.kernels.attn == impl
        eng.warmup("full")
        con = json_constraint(eng.tokenizer, TOOLPROMPT_SCHEMA)
        sid = eng.begin_request(
            PROMPT, SamplingParams(max_tokens=24), mask_fn=con
        )
        while not eng.prefill_step(sid):
            pass
        # Counter window covers the ffwd/mixed decode dispatches — the
        # kernel shapes under test. (Admission's masked single-row
        # sampler is a pre-existing once-per-process compile, primed by
        # earlier tests in file order but not in standalone runs.)
        n0 = len(_COMPILES)
        _drive_async(eng, sid)
        assert len(_COMPILES) == n0, (
            f"{len(_COMPILES) - n0} post-warmup compiles on {impl} {kw}"
        )
        return eng.finish(sid)

    want = run("xla")
    with stream_kernel():
        assert run("pallas-stream") == want
        run("xla", kv_quantize="int8")      # the kernel has no int8 reader
