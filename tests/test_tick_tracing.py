"""Tick phases, the step clock, the scopes of the step programs and the
token hand-off lag (obs/tick.py, models/llama.py SCOPES, serving/api.py).

On the tiny CPU engine: the phases of a scheduler run partition the loop's
wall time and a slow pull lands in ``wait``; the step clock's rule on a
synthetic clock; every scope of the vocabulary on the ``op_name`` metadata
of the step programs, and the compiled program unchanged by them; one
``emit_lag`` sample for each streamed content chunk; one tick id on the
flight event and on the request's span children.
"""

import asyncio
import contextlib
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opsagent_tpu import obs
from opsagent_tpu.models import llama
from opsagent_tpu.models.config import get_config_preset
from opsagent_tpu.serving import decode_loop
from opsagent_tpu.serving import engine as engine_mod
from opsagent_tpu.serving.engine import Engine, EngineConfig
from opsagent_tpu.serving.sampler import SamplingParams, sample
from opsagent_tpu.serving.scheduler import Request, Scheduler

BASE = dict(
    model="tiny-test", dtype=jnp.float32, tp=1, page_size=4,
    num_pages=128, max_pages_per_seq=24, max_batch_size=4,
    prefill_buckets=(8, 16), decode_block=4,
    mixed_buckets=(4, 8, 16), max_step_tokens=32, warmup=False,
)


@pytest.fixture(scope="module")
def engine():
    return Engine(EngineConfig(**BASE))


def phase_seconds() -> dict[str, float]:
    return {p: obs.TICK_PHASE_SECONDS.value(phase=p) for p in obs.TICK_PHASES}


def run_requests(sched: Scheduler, n: int, max_tokens: int = 12) -> list:
    reqs = [
        Request(list(range(5, 25 + i)),
                SamplingParams(max_tokens=max_tokens, temperature=0.0))
        for i in range(n)
    ]
    for r in reqs:
        sched.submit(r)
    for r in reqs:
        assert r.done.wait(300), "request did not finish"
        assert not r.error, r.error
    return reqs


# -- obs.phase ----------------------------------------------------------------
def test_a_nested_phase_suspends_the_outer_one():
    before = phase_seconds()
    t0 = time.perf_counter()
    with obs.phase("plan"):
        time.sleep(0.02)
        with obs.phase("wait", tick=3):
            time.sleep(0.05)
        time.sleep(0.01)
    wall = time.perf_counter() - t0
    got = {p: v - before[p] for p, v in phase_seconds().items()}
    assert got["wait"] == pytest.approx(0.05, abs=0.015)
    assert got["plan"] == pytest.approx(0.03, abs=0.015)
    # no overlap and no hole: one clock reading ends a phase and starts
    # the next
    assert sum(got.values()) == pytest.approx(wall, abs=1e-3)


def test_phases_partition_the_loop_and_a_slow_pull_is_wait(engine, monkeypatch):
    """The scheduler thread is always in exactly one phase: over a run the
    phases' seconds add up to the wall time, and the time of a slow device
    pull is ``wait``'s, not ``commit``'s."""
    slow_s = 0.03

    class SlowPull:
        """``np`` as ``Engine._pull`` sees it: ``asarray`` takes a while."""

        def asarray(self, x, *a, **kw):
            time.sleep(slow_s)
            return np.asarray(x, *a, **kw)

        def __getattr__(self, name):
            return getattr(np, name)

    sched = Scheduler(engine)
    sched.start()
    try:
        run_requests(sched, 2)          # compiles outside the measured run
        pulls_before = obs.STEP_LATE_PULLS.value(program="mixed") + sum(
            obs.STEP_DEVICE_SECONDS.count(program="mixed", bucket=str(b))
            for b in BASE["mixed_buckets"])
        monkeypatch.setattr(engine_mod, "np", SlowPull())
        time.sleep(0.12)                # the loop is idle: let a wait end
        before, t0 = phase_seconds(), time.perf_counter()
        ticks0 = obs.TICKS.value()
        run_requests(sched, 4, max_tokens=16)
        time.sleep(0.12)
        after, wall = phase_seconds(), time.perf_counter() - t0
    finally:
        monkeypatch.undo()
        sched.stop()
    got = {p: after[p] - before[p] for p in obs.TICK_PHASES}
    # the one phase in progress at each reading is not counted yet: an idle
    # wait of at most 50 ms
    assert sum(got.values()) == pytest.approx(wall, rel=0.05, abs=0.06)
    pulls = obs.STEP_LATE_PULLS.value(program="mixed") + sum(
        obs.STEP_DEVICE_SECONDS.count(program="mixed", bucket=str(b))
        for b in BASE["mixed_buckets"]) - pulls_before
    block_pulls = got["wait"] / slow_s
    assert pulls >= 1 and block_pulls >= pulls
    assert got["wait"] >= 0.9 * slow_s * pulls
    assert got["commit"] < got["wait"] / 3
    assert obs.TICKS.value() - ticks0 >= pulls
    assert all(got[p] > 0 for p in ("admit", "plan", "dispatch", "reap", "idle"))


# -- the step clock -----------------------------------------------------------
class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self) -> float:
        return self.now


def drive(clock, events) -> tuple[list[float], float]:
    """Run (time, verb, step, waited) events through a StepClock; returns
    the samples it took and the late pulls it counted."""
    name = f"test{id(events)}"
    sc = obs.StepClock(clock=clock)
    tickets = {}
    sums = []
    for at, verb, step, waited in events:
        clock.now = at
        if verb == "enqueue":
            tickets[step] = sc.enqueue()
        else:
            s0 = obs.STEP_DEVICE_SECONDS.sum(program=name, bucket="8")
            n0 = obs.STEP_DEVICE_SECONDS.count(program=name, bucket="8")
            sc.pulled(name, 8, tickets[step], waited)
            if obs.STEP_DEVICE_SECONDS.count(program=name, bucket="8") > n0:
                sums.append(
                    obs.STEP_DEVICE_SECONDS.sum(program=name, bucket="8") - s0)
    return sums, obs.STEP_LATE_PULLS.value(program=name)


STEP_CLOCK_CASES = {
    # sync: enqueue, wait, pull -> ready - enqueued
    "sync_steps": (
        [(1.0, "enqueue", 1, None), (1.5, "pull", 1, True),
         (2.0, "enqueue", 2, None), (2.7, "pull", 2, True)],
        [0.5, 0.7], 0),
    # depth 2: step 2 is enqueued before step 1 is pulled, so it starts
    # when step 1 is ready: ready_2 - max(ready_1, enqueued_2)
    "lookahead_starts_at_the_previous_ready": (
        [(1.0, "enqueue", 1, None), (1.1, "enqueue", 2, None),
         (1.6, "pull", 1, True), (2.2, "pull", 2, True)],
        [0.6, 0.6], 0),
    # a pull that found its result ready: no sample, one late pull
    "late_pull_adds_no_sample": (
        [(1.0, "enqueue", 1, None), (3.0, "pull", 1, False)],
        [], 1),
    # after a late pull the next step's start is unknown if it was already
    # enqueued (it started somewhere before the host arrived) ...
    "start_unknown_after_a_late_pull": (
        [(1.0, "enqueue", 1, None), (1.1, "enqueue", 2, None),
         (3.0, "pull", 1, False), (3.4, "pull", 2, True)],
        [], 2),
    # ... and known again once a step is enqueued on a drained device
    "enqueued_on_a_drained_device": (
        [(1.0, "enqueue", 1, None), (3.0, "pull", 1, False),
         (3.5, "enqueue", 2, None), (4.25, "pull", 2, True)],
        [0.75], 1),
    # a step that is never pulled (a prefill chunk that does not finish
    # its prompt) hides the start of the one after it
    "an_unpulled_step_breaks_the_chain": (
        [(1.0, "enqueue", 1, None), (1.5, "pull", 1, True),
         (2.0, "enqueue", 2, None), (2.1, "enqueue", 3, None),
         (3.0, "pull", 3, True), (3.1, "enqueue", 4, None),
         (3.6, "pull", 4, True)],
        [0.5, 0.5], 1),
}


@pytest.mark.parametrize("case", sorted(STEP_CLOCK_CASES))
def test_step_clock_on_a_synthetic_clock(case):
    events, samples, late = STEP_CLOCK_CASES[case]
    got, got_late = drive(FakeClock(), events)
    assert got == pytest.approx(samples)
    assert got_late == late


def test_the_engine_clocks_every_program_it_pulls(engine):
    """A scheduler run leaves samples or late pulls for the mixed step and
    the decode block, with no profiler and no extra sync."""
    sched = Scheduler(engine)
    sched.start()
    try:
        run_requests(sched, 3, max_tokens=20)
    finally:
        sched.stop()
    snap = obs.metrics_snapshot()
    for program in ("mixed", "decode_block"):
        n = sum(v for k, v in snap.items() if k.endswith("_count")
                and k.startswith("opsagent_step_device_seconds")
                and f'program="{program}"' in k)
        late = snap.get(
            f'opsagent_step_late_pulls_total{{program="{program}"}}', 0)
        assert n + late > 0, program


# -- named scopes -------------------------------------------------------------
def _shapes():
    cfg = get_config_preset("tiny-test")
    B, S, MaxP = 2, 4, 6
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)      # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)    # noqa: E731
    bl = lambda *s: jax.ShapeDtypeStruct(s, jnp.bool_)       # noqa: E731
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    cache = jax.eval_shape(lambda: llama.make_cache(cfg, 16, 4, jnp.float32))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    table = i32(B, MaxP)
    return cfg, B, S, i32, f32, bl, params, cache, key, table


def lower_program(name: str):
    cfg, B, S, i32, f32, bl, params, cache, key, table = _shapes()
    f32dt = dict(dtype=jnp.float32)
    if name == "mixed":
        def f(params, tokens, use_carry, carry, starts, qlens, emits, cache,
              table, key, temps, top_k, top_p, fm, fd, cf, of):
            return decode_loop.mixed_step_carry(
                params, cfg, tokens, use_carry, carry, starts, qlens, emits,
                cache, table, key, temps, top_k, top_p, fsm_mask=fm,
                fsm_dest=fd, carry_fsm=cf, ov_fsm=of, **f32dt)
        args = (params, i32(B, S), bl(B), i32(B), i32(B), i32(B), bl(B),
                cache, table, key, f32(B), i32(B), f32(B),
                bl(5, cfg.vocab_size), i32(5, cfg.vocab_size), i32(B), i32(B))
    elif name == "decode_block":
        def f(params, tok, at, eos, key, override, ov_tok, ov_at, alive,
              budgets, cache, table, temps, top_k, top_p):
            return decode_loop.decode_block_carry(
                params, cfg, tok, at, eos, key, override, ov_tok, ov_at,
                alive, budgets, cache, table, temps, top_k, top_p,
                jnp.int32(1), jnp.int32(0), 4, **f32dt)
        args = (params, i32(B), i32(B), bl(B), key, bl(B), i32(B), i32(B),
                bl(B), i32(B), cache, table, f32(B), i32(B), f32(B))
    elif name == "prefill_chunk":
        def f(params, tokens, start, lengths, cache, table):
            return llama.prefill_with_prefix(
                params, cfg, tokens, start, lengths, cache, table, **f32dt)
        args = (params, i32(B, S), i32(B), i32(B), cache, table)
    else:
        f = sample
        args = (f32(B, cfg.vocab_size), key, f32(B), i32(B), f32(B), None)
    return jax.jit(f).lower(*args)


def scopes_in(compiled_text: str) -> set[str]:
    """The vocabulary names on the ``op_name`` metadata of a compiled
    program: what a device trace shows as each operation's ``tf_op``."""
    found = set()
    for path in re.findall(r'op_name="([^"]+)"', compiled_text):
        found.update(p for p in path.split("/") if p in llama.SCOPES)
    return found


@pytest.mark.parametrize("program,missing", [
    ("mixed", set()),
    ("decode_block", set()),
    # the prefill programs hand their logits to the sample program
    ("prefill_chunk", {"sample"}),
    ("sample", set(llama.SCOPES) - {"sample"}),
])
def test_every_scope_of_the_vocabulary_is_on_the_programs(program, missing):
    text = lower_program(program).compile().as_text()
    assert scopes_in(text) == set(llama.SCOPES) - missing


def test_the_benchmark_reads_the_same_vocabulary():
    from benchmarks import scope_reduce

    assert scope_reduce.SCOPES == llama.SCOPES


def test_scopes_change_no_instruction_of_the_compiled_step(monkeypatch):
    """``jax.named_scope`` is metadata: the optimised HLO of the mixed step
    has the same instructions with the scopes and without them."""
    def instructions(text: str) -> list[str]:
        """The instruction lines of every computation, less their metadata
        (op_name, source line, stack frame: what the scopes do change)."""
        text = re.sub(r", metadata=\{[^}]*\}", "", text)
        # XLA names some instructions after their op_name: number every
        # name by its first appearance instead
        names: dict[str, str] = {}
        text = re.sub(
            r"%[\w.\-]+",
            lambda m: names.setdefault(m.group(0), f"%n{len(names)}"), text)
        return [ln for ln in text.splitlines()
                if re.match(r"\s+(ROOT )?%n\d+ = ", ln)]

    # The persistent compile cache keys a program without its metadata:
    # it would hand the second compile the first one's executable, names
    # and all (PERF.md, PR 24's trap), so it is off around these two.
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with_scopes = lower_program("mixed").compile().as_text()
        monkeypatch.setattr(
            jax, "named_scope", lambda name: contextlib.nullcontext())
        without = lower_program("mixed").compile().as_text()
    finally:
        monkeypatch.undo()
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
    assert scopes_in(with_scopes) == set(llama.SCOPES)
    # (JAX's own threefry code puts a "sample" on its operations' paths)
    assert not scopes_in(without) - {"sample"}
    assert len(instructions(with_scopes)) > 100
    assert instructions(with_scopes) == instructions(without)


# -- token hand-off lag and the tick id -----------------------------------------
def test_emit_lag_observes_once_for_each_streamed_chunk(engine):
    from aiohttp.test_utils import TestClient, TestServer

    from opsagent_tpu.serving.api import ServingStack, build_engine_app

    stack = ServingStack(engine)
    app = build_engine_app(stack)

    async def scenario() -> int:
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.post("/v1/chat/completions", json={
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 9, "stream": True})
            assert r.status == 200
            body = await r.text()
        finally:
            await client.close()
        events = [json.loads(ln[6:]) for ln in body.splitlines()
                  if ln.startswith("data: {")]
        return sum(1 for e in events
                   if e["choices"][0]["delta"].get("content"))

    n0 = obs.STREAM_EMIT_LAG_SECONDS.count()
    s0 = obs.STREAM_EMIT_LAG_SECONDS.sum()
    try:
        chunks = asyncio.new_event_loop().run_until_complete(scenario())
    finally:
        stack.close()
    assert chunks > 0
    assert obs.STREAM_EMIT_LAG_SECONDS.count() - n0 == chunks
    lag = (obs.STREAM_EMIT_LAG_SECONDS.sum() - s0) / chunks
    assert 0 <= lag < 5.0


def test_one_tick_id_joins_the_flight_event_and_the_request_spans(engine):
    sched = Scheduler(engine)
    sched.start()
    try:
        with obs.trace_request("tick-join") as trace:
            req = Request(list(range(5, 30)),
                          SamplingParams(max_tokens=10, temperature=0.0),
                          trace=trace.root.start_child("generate"))
            sched.submit(req)
            assert req.done.wait(300) and not req.error
    finally:
        sched.stop()
    ticks = set()

    def walk(span):
        if "tick" in span.attrs:
            ticks.add(span.attrs["tick"])
        for c in span.children:
            walk(c)

    walk(trace.root)
    assert ticks, "no span child carries a tick id"
    dispatched = {e["tick"] for e in obs.flight.get_recorder().snapshot()
                  if e.get("kind") == "dispatch" and "tick" in e}
    assert ticks <= dispatched
