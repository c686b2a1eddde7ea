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
    def sleep(seconds):
        """What the sleep took: on a loaded machine, more than was asked."""
        t = time.perf_counter()
        time.sleep(seconds)
        return time.perf_counter() - t

    before = phase_seconds()
    t0 = time.perf_counter()
    with obs.phase("plan"):
        plan = sleep(0.02)
        with obs.phase("wait", tick=3):
            wait = sleep(0.05)
        plan += sleep(0.01)
    wall = time.perf_counter() - t0
    got = {p: v - before[p] for p, v in phase_seconds().items()}
    assert got["wait"] == pytest.approx(wait, abs=0.015)
    assert got["plan"] == pytest.approx(plan, abs=0.015)
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


# -- parts of a phase (PR 38) ---------------------------------------------------
from opsagent_tpu.obs import tick as tick_mod  # noqa: E402
from opsagent_tpu.serving import faults  # noqa: E402


def part_seconds() -> dict[tuple[str, str], float]:
    """{(phase, part): seconds} of opsagent_tick_part_seconds_total."""
    out = {}
    for key, v in obs.metrics_snapshot().items():
        m = re.match(
            r'opsagent_tick_part_seconds_total\{phase="(\w+)",part="(\w+)"\}',
            key)
        if m:
            out[m.groups()] = v
    return out


@pytest.fixture
def scripted(monkeypatch):
    """obs/tick.py on a clock the test moves, with annotations that record
    their opening and closing instead of reaching the profiler."""
    clock = FakeClock()
    events: list[tuple[str, str]] = []

    class Annotation:
        def __init__(self, label, **ids):
            self.label = label

        def __enter__(self):
            events.append(("open", self.label))

        def __exit__(self, *exc):
            events.append(("close", self.label))

    class Time:
        perf_counter = staticmethod(clock)

    monkeypatch.setattr(tick_mod, "_annotation", Annotation)
    monkeypatch.setattr(tick_mod, "time", Time)
    return clock, events


def admit_script(clock, parts: bool) -> None:
    """1 s of ``admit``, 2 s of its ``match`` (with the parts stubbed: of
    ``admit`` itself), 0.5 s of ``admit`` again."""
    match = (obs.phase("admit", part="match") if parts
             else contextlib.nullcontext())
    with obs.phase("admit"):
        clock.now += 1.0
        with match:
            clock.now += 2.0
        clock.now += 0.5


def test_a_part_suspends_its_phase_and_both_counters_get_its_seconds(scripted):
    clock, events = scripted
    totals = []
    for parts in (False, True):
        del events[:]
        p0, m0 = phase_seconds()["admit"], obs.TICK_PART_SECONDS.value(
            phase="admit", part="match")
        admit_script(clock, parts)
        totals.append(phase_seconds()["admit"] - p0)
        got_part = obs.TICK_PART_SECONDS.value(
            phase="admit", part="match") - m0
        assert got_part == pytest.approx(2.0 if parts else 0.0)
    # the phase's total is what it was with the parts stubbed
    assert totals == pytest.approx([3.5, 3.5])
    # the thread was in engine.admit, then in engine.admit.match and NOT in
    # engine.admit, then in engine.admit again: never in two spans at once
    assert events == [
        ("open", "engine.admit"), ("close", "engine.admit"),
        ("open", "engine.admit.match"), ("close", "engine.admit.match"),
        ("open", "engine.admit"), ("close", "engine.admit"),
    ]


def test_add_part_sums_without_an_annotation(scripted):
    clock, events = scripted
    before = part_seconds()
    plan0 = phase_seconds()["plan"]
    with obs.phase("plan", part="arrays"):
        clock.now += 1.0
        for _ in range(2):      # a cost a row, timed by the caller
            obs.add_part("plan", "pages", 0.25)
    got = {k: v - before.get(k, 0.0) for k, v in part_seconds().items()}
    # the seconds came out of the part that was open: nothing counts twice
    assert got[("plan", "pages")] == pytest.approx(0.5)
    assert got[("plan", "arrays")] == pytest.approx(0.5)
    assert phase_seconds()["plan"] - plan0 == pytest.approx(1.0)
    assert [label for _, label in events] == ["engine.plan.arrays"] * 2
    # under no part the seconds leave the phase's `other`, and with no
    # phase open the counter still gets them
    with obs.phase("plan"):
        clock.now += 1.0
        obs.add_part("plan", "pages", 0.125)
    obs.add_part("plan", "pages", 0.125)
    assert (obs.TICK_PART_SECONDS.value(phase="plan", part="pages")
            - before.get(("plan", "pages"), 0.0)) == pytest.approx(0.75)
    assert phase_seconds()["plan"] - plan0 == pytest.approx(2.0)
    # one tick's host work is what the work phases took since it was asked
    obs.take_host_work()
    with obs.phase("commit"):
        clock.now += 0.3
        with obs.phase("wait"):
            clock.now += 5.0
    assert obs.take_host_work() == pytest.approx(0.3)
    assert obs.take_host_work() == 0.0


def test_the_parts_of_a_driven_engine_partition_their_phases(
        engine, monkeypatch):
    """Over a scheduler run: the parts of each phase never add up to more
    than the phase (what is left is its ``other``), the phases still
    partition the loop, ``wait`` is ``alone`` exactly when nothing is
    enqueued behind the step pulled, one observation of a tick's host work
    a tick, and the ticks a token takes are counted for finished requests."""
    pulls = []
    real_pull = Engine._pull

    def spy(self, program, bucket, ticket, out_d, alone=True):
        # what is enqueued behind the step pulled: a younger async tick, a
        # younger block; a prefill chunk's pull follows its own dispatch
        behind = {"mixed": len(self._async._pending),
                  "decode_block": len(self._inflight)}.get(program, 0)
        pulls.append((program, alone, behind))
        return real_pull(self, program, bucket, ticket, out_d, alone=alone)

    sched = Scheduler(engine)
    sched.start()
    try:
        run_requests(sched, 2)          # compiles outside the measured run
        time.sleep(0.12)
        monkeypatch.setattr(Engine, "_pull", spy)
        parts0, phases0, t0 = part_seconds(), phase_seconds(), time.perf_counter()
        ticks0 = obs.TICKS.value()
        work0 = obs.TICK_HOST_WORK_SECONDS.count()
        dt0 = (obs.REQUEST_DECODE_TICKS.value(),
               obs.REQUEST_DECODE_TOKENS.value())
        # prompts the trie has not seen, several chunks long: the async
        # lane runs ticks back to back, a younger one behind each pull
        reqs = [
            Request([(53 * i + 3 * j) % 199 + 5 for j in range(60)],
                    SamplingParams(max_tokens=16, temperature=0.0))
            for i in range(1, 5)
        ]
        for r in reqs:
            sched.submit(r)
        for r in reqs:
            assert r.done.wait(300) and not r.error, r.error
        time.sleep(0.12)
        parts1, phases1 = part_seconds(), phase_seconds()
        wall = time.perf_counter() - t0
    finally:
        monkeypatch.undo()
        sched.stop()
    phases = {p: phases1[p] - phases0[p] for p in obs.TICK_PHASES}
    assert sum(phases.values()) == pytest.approx(wall, rel=0.05, abs=0.06)
    parts: dict[str, dict[str, float]] = {}
    for (phase, part), v in parts1.items():
        parts.setdefault(phase, {})[part] = v - parts0.get((phase, part), 0.0)
    for phase, rows in parts.items():
        assert all(v >= 0 for v in rows.values()), (phase, rows)
        other = phases[phase] - sum(rows.values())
        # (the snapshot rounds each part to a microsecond)
        assert other >= -1e-5, (phase, rows, phases[phase])
    # the parts the records point at are there, where the work is
    for phase, part in [
        ("admit", "drain"), ("admit", "match"), ("admit", "alloc"),
        ("admit", "register"), ("admit", "account"), ("plan", "route"),
        ("plan", "chunks"), ("plan", "rows"), ("plan", "lanes"),
        ("plan", "arrays"), ("plan", "pages"), ("plan", "account"),
        ("plan", "book"), ("dispatch", "place"), ("dispatch", "call"),
        ("commit", "accept"), ("commit", "account"), ("reap", "finish"),
        ("reap", "account"),
    ]:
        assert parts[phase][part] > 0, (phase, part)
    # a wait is one part or the other, and wholly so
    assert set(parts["wait"]) <= {"alone", "pipelined"}
    assert sum(parts["wait"].values()) == pytest.approx(
        phases["wait"], abs=1e-5)
    # dispatch is place and call and a sliver between them
    assert sum(parts["dispatch"].values()) >= 0.8 * phases["dispatch"]
    # alone exactly when nothing is enqueued behind the step pulled
    assert pulls and all(alone == (behind == 0) for _, alone, behind in pulls)
    assert {alone for program, alone, _ in pulls if program == "mixed"} == {
        True, False}
    # one observation of a tick's host work for each tick counted
    ticks = obs.TICKS.value() - ticks0
    assert ticks > 0
    assert obs.TICK_HOST_WORK_SECONDS.count() - work0 == ticks
    # every finished request's intervals are counted, and a tick gives a
    # row a token or, in a fused block, several: never fewer ticks than
    # none, never more than the run had
    tokens = sum(len(r.tokens) - 1 for r in reqs)
    assert obs.REQUEST_DECODE_TOKENS.value() - dt0[1] == tokens
    assert 0 < obs.REQUEST_DECODE_TICKS.value() - dt0[0] <= ticks * len(reqs)


def test_one_admission_observation_an_attempt_by_outcome(engine):
    def counts():
        return {o: obs.ADMISSION_SECONDS.count(outcome=o)
                for o in ("admitted", "out_of_pages", "rejected")}

    sched = Scheduler(engine)
    sched.start()
    before = counts()
    try:
        faults.configure("sched.out_of_pages@1..3")
        ok = sched.submit(
            Request(list(range(5, 15)), SamplingParams(max_tokens=3)))
        assert ok.done.wait(120) and not ok.error, ok.error
        faults.reset()
        # a prompt past the model's window is refused at begin_request
        bad = sched.submit(Request(
            [5] * (engine.model_cfg.max_position + 1),
            SamplingParams(max_tokens=3)))
        assert bad.done.wait(120) and bad.error
    finally:
        faults.reset()
        sched.stop()
    got = {o: n - before[o] for o, n in counts().items()}
    # three attempts ended in the injected OutOfPages and were made again,
    # the fourth admitted: attempts, not one long wait
    assert got == {"admitted": 1, "out_of_pages": 3, "rejected": 1}


def test_ticks_per_token_is_one_for_a_plain_lane_and_under_for_a_fast_forward(
        engine):
    """The scheduler's reap adds a finished request's ticks and tokens from
    the stamps ``_accept_token`` left: a lane that gets one token a tick
    reads 1, one whose dispatch appended a forced run reads under 1."""
    from opsagent_tpu.serving.engine import Sequence

    sched = Scheduler(engine)       # never started: the test is the loop

    def lane(arrivals: list[int]) -> float:
        """A request whose tokens arrive at the given ticks, reaped."""
        sid = engine.begin_request(
            list(range(5, 12)), SamplingParams(max_tokens=len(arrivals)))
        seq = engine.sequences[sid]
        assert isinstance(seq, Sequence)
        engine._prefilling.pop(sid, None)
        for at in arrivals:
            engine.sched_tick = at
            engine._accept_token(seq, 7)
        assert seq.done
        req = Request(list(range(5, 12)), SamplingParams(max_tokens=4))
        req.seq_id = sid
        sched._running[sid] = req
        t0 = obs.REQUEST_DECODE_TICKS.value()
        n0 = obs.REQUEST_DECODE_TOKENS.value()
        sched._reap()
        assert req.done.is_set() and len(req.tokens) == len(arrivals)
        return ((obs.REQUEST_DECODE_TICKS.value() - t0)
                / (obs.REQUEST_DECODE_TOKENS.value() - n0))

    tick0 = engine.sched_tick
    try:
        assert lane([40, 41, 42, 43, 44]) == pytest.approx(1.0)
        # a forced run of three lands with the token sampled after it
        assert lane([50, 51, 51, 51, 51, 52]) == pytest.approx(0.4)
    finally:
        engine.sched_tick = tick0


def test_the_step_clock_labels_a_sample_with_the_width_its_ticket_carries():
    clock = FakeClock()
    sc = obs.StepClock(clock=clock)
    name = "test_width"

    def samples(**labels):
        return (obs.STEP_DEVICE_SECONDS.count(program=name, **labels),
                obs.STEP_DEVICE_SECONDS.sum(program=name, **labels))

    clock.now = 1.0
    narrow = sc.enqueue("128")
    clock.now = 1.5
    sc.pulled(name, 16, narrow, True)
    clock.now = 2.0
    wide = sc.enqueue("256")
    clock.now = 2.75
    sc.pulled(name, 16, wide, True)
    clock.now = 3.0
    plain = sc.enqueue()            # a program that is not mixed
    clock.now = 3.25
    sc.pulled(name, 16, plain, True)
    assert narrow[2] == "128" and plain[2] == ""
    assert samples(bucket="16", width="128") == (1, pytest.approx(0.5))
    assert samples(bucket="16", width="256") == (1, pytest.approx(0.75))
    assert samples(bucket="16", width="") == (1, pytest.approx(0.25))
    # a reader that names fewer labels sums over the rest, as the
    # benchmark's `client.total` does over a scrape
    assert samples(bucket="16") == (3, pytest.approx(1.5))
    assert samples() == (3, pytest.approx(1.5))
    assert 'width="128"' in obs.get_registry().render()
