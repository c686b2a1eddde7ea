"""Fleet failure containment (serving/faults + router failover): the
deterministic fault-injection harness, the per-replica circuit breaker
(healthy -> suspect -> ejected -> half-open probe), connect-phase retry
with re-route, mid-SSE failover that resumes the client stream
byte-identically, TTFT hedging, overload shedding (429 + Retry-After),
and the scheduler/agent fault points.

The acceptance gate (ISSUE 9): kill a replica mid-decode in a 2-replica
in-process fleet under a seeded fault spec — every in-flight request
completes on the surviving replica with zero client-visible errors, the
streamed text has no gaps or duplicated tokens at the failover seam, and
the greedy output is byte-identical to a fault-free run.
"""

import asyncio
import json
import threading
import time
import urllib.error
import urllib.request

import jax.numpy as jnp
import pytest

from opsagent_tpu import obs
from opsagent_tpu.serving import faults
from opsagent_tpu.serving.api import ServingStack
from opsagent_tpu.serving.engine import Engine, EngineConfig
from opsagent_tpu.serving.fleet.registry import (
    EJECT_AFTER_FAILURES,
    ReplicaInfo,
    ReplicaRegistry,
)
from opsagent_tpu.serving.fleet.router import (
    FleetRouter,
    OverloadError,
    build_router_app,
)
from opsagent_tpu.serving.sampler import SamplingParams
from opsagent_tpu.serving.scheduler import Request, Scheduler

BASE = dict(
    model="tiny-test", dtype=jnp.float32, tp=1, page_size=4,
    num_pages=256, max_pages_per_seq=64, max_batch_size=4,
    prefill_buckets=(16, 32, 64), decode_block=4, seed=0,
    offload=True,
)


def _fleet(n=2, **router_kw):
    router = FleetRouter(**router_kw)
    stacks = []
    for i in range(n):
        stack = ServingStack(Engine(EngineConfig(**BASE)))
        stacks.append(stack)
        router.add_local(stack, f"r{i}")
    return router, stacks


def _close(stacks):
    for s in stacks:
        s.close()


def _flight(kind):
    return obs.flight.get_recorder().snapshot(kind=kind)


# -- fault-spec determinism ---------------------------------------------------
class TestFaultSpec:
    def test_count_based_selectors(self):
        faults.configure("a@2;b@2..3;c@3+;d@every:2")
        assert [faults.fire("a") for _ in range(4)] == \
            [False, True, False, False]
        assert [faults.fire("b") for _ in range(4)] == \
            [False, True, True, False]
        assert [faults.fire("c") for _ in range(4)] == \
            [False, False, True, True]
        assert [faults.fire("d") for _ in range(4)] == \
            [False, True, False, True]
        assert not faults.fire("unwired")

    def test_prob_selector_is_seed_deterministic(self):
        faults.configure("x@p:0.5:42")
        first = [faults.fire("x") for _ in range(64)]
        faults.configure("x@p:0.5:42")
        assert [faults.fire("x") for _ in range(64)] == first
        faults.configure("x@p:0.5:43")
        assert [faults.fire("x") for _ in range(64)] != first
        assert any(first) and not all(first)

    def test_same_spec_same_flight_event_sequence(self):
        """The reproducibility acceptance criterion, at the harness level:
        the same spec against the same hit sequence fires identically and
        records the identical fault_injected event sequence."""
        def drive():
            faults.configure("p1@2;p2@every:3")
            for _ in range(9):
                faults.fire("p1")
                faults.fire("p2")
            return [
                (e["point"], e["hit"])
                for e in _flight("fault_injected")
            ]

        first = drive()
        obs.flight.get_recorder().reset()
        assert drive() == first
        assert first == [("p1", 2), ("p2", 3), ("p2", 6), ("p2", 9)]

    def test_malformed_clause_skipped_and_firing_recorded(self):
        faults.configure("not a clause;ok@1")
        assert faults.active()
        assert faults.fire("ok", extra="ctx")
        assert not faults.fire("ok")
        assert obs.FAULT_INJECTIONS.value(point="ok") == 1
        events = _flight("fault_injected")
        assert events and events[-1]["point"] == "ok"
        s = faults.summary()
        assert s["fired"] == {"ok": 1} and s["hits"] == {"ok": 2}

    def test_env_spec_loads_lazily(self, monkeypatch):
        faults.reset()
        monkeypatch.setenv(faults.ENV_FAULTS, "envpoint@1")
        assert faults.fire("envpoint")
        assert not faults.fire("envpoint")

    def test_maybe_raise_class_and_instance(self):
        faults.configure("e@1..2")
        with pytest.raises(TimeoutError, match="injected"):
            faults.maybe_raise("e", TimeoutError, "injected timeout")
        with pytest.raises(ConnectionError, match="boom"):
            faults.maybe_raise("e", ConnectionError("boom"))
        faults.maybe_raise("e", RuntimeError)  # hit 3: no fire, no raise


# -- circuit breaker ----------------------------------------------------------
class TestCircuitBreaker:
    def _reg(self, cooldown=0.2):
        reg = ReplicaRegistry(eject_cooldown=cooldown)
        reg.register(ReplicaInfo(replica_id="a", local=True))
        reg.register(ReplicaInfo(replica_id="b", local=True))
        return reg

    def test_failures_walk_healthy_suspect_ejected(self):
        reg = self._reg()
        reg.note_result("a", ok=False)
        assert reg.health_of("a").state == "suspect"
        assert {i.replica_id for i in reg.alive()} == {"a", "b"}
        for _ in range(EJECT_AFTER_FAILURES - 1):
            reg.note_result("a", ok=False)
        assert reg.health_of("a").state == "ejected"
        assert [i.replica_id for i in reg.alive()] == ["b"]
        assert obs.FLEET_EJECTIONS.value() == 1
        assert _flight("replica_ejected")[-1]["replica"] == "a"
        # Non-admitting reads still see the ejected replica.
        assert {i.replica_id for i in reg.alive(admitting=False)} == \
            {"a", "b"}

    def test_success_closes_the_breaker(self):
        reg = self._reg()
        reg.note_result("a", ok=False)
        reg.note_result("a", ok=False)
        reg.note_result("a", ok=True)
        h = reg.health_of("a")
        assert h.state == "healthy" and h.consecutive_failures == 0

    def test_half_open_probe_gates_readmission(self):
        reg = self._reg(cooldown=0.15)
        for _ in range(EJECT_AFTER_FAILURES):
            reg.note_result("a", ok=False)
        assert [i.replica_id for i in reg.alive()] == ["b"]
        time.sleep(0.2)
        # Cooldown elapsed: half-open, admitting again.
        assert {i.replica_id for i in reg.alive()} == {"a", "b"}
        reg.begin_probe("a")
        # One probe in flight: no second request admitted.
        assert [i.replica_id for i in reg.alive()] == ["b"]
        reg.note_result("a", ok=True)
        assert reg.health_of("a").state == "healthy"
        assert {i.replica_id for i in reg.alive()} == {"a", "b"}

    def test_failed_probe_reejects_with_backoff(self):
        reg = self._reg(cooldown=0.15)
        for _ in range(EJECT_AFTER_FAILURES):
            reg.note_result("a", ok=False)
        time.sleep(0.2)
        reg.begin_probe("a")
        reg.note_result("a", ok=False)  # the probe failed
        h = reg.health_of("a")
        assert h.state == "ejected" and h.ejections == 2
        # Doubled cooldown: ~0.3 s remaining, not ~0.15.
        assert h.ejected_until - time.monotonic() > 0.2

    def test_heartbeat_staleness_marks_remote_suspect(self):
        reg = ReplicaRegistry(ttl_s=0.5)
        reg.register(ReplicaInfo(replica_id="far", url="http://x"))
        assert [i.replica_id for i in reg.alive()] == ["far"]
        assert reg.health_of("far").state == "healthy"
        time.sleep(0.3)  # > ttl/2, < ttl
        assert [i.replica_id for i in reg.alive()] == ["far"]
        assert reg.health_of("far").state == "suspect"

    def test_reregistration_resets_health(self):
        reg = self._reg()
        for _ in range(EJECT_AFTER_FAILURES):
            reg.note_result("a", ok=False)
        reg.register(ReplicaInfo(replica_id="a", local=True))
        assert reg.health_of("a").state == "healthy"
        assert {i.replica_id for i in reg.alive()} == {"a", "b"}


# -- router failover ----------------------------------------------------------
class _Flaky:
    """Replica-handle proxy whose chat_completion fails while the shared
    budget lasts — whichever replica the router picks first eats it."""

    def __init__(self, inner, budget, exc=None):
        self._inner = inner
        self._budget = budget
        self._exc = exc or ConnectionError("injected connect failure")

    def chat_completion(self, body):
        if self._budget["n"] > 0:
            self._budget["n"] -= 1
            raise self._exc
        return self._inner.chat_completion(body)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class TestRouterFailover:
    BODY = {
        "messages": [{"role": "user", "content": "contain this failure"}],
        "max_tokens": 8, "temperature": 0,
    }

    def test_connect_failure_retries_on_another_replica(self):
        router, stacks = _fleet(2)
        try:
            budget = {"n": 1}
            for rid in ("r0", "r1"):
                info = router.registry.get(rid)
                info.handle = _Flaky(info.handle, budget)
            resp = router.complete(dict(self.BODY))
            text = resp["choices"][0]["message"]["content"]
            assert text
            assert budget["n"] == 0
            assert obs.FLEET_RETRIES.value() == 1
            retries = _flight("fleet_retry")
            assert retries and retries[-1]["attempt"] == 1
            # The failed call fed the breaker.
            states = set(router.registry.health_snapshot().values())
            assert "suspect" in states
        finally:
            _close(stacks)

    def test_non_retryable_400_is_not_retried(self):
        router, stacks = _fleet(2)
        try:
            from opsagent_tpu.serving.scheduler import RequestError

            budget = {"n": 4}
            err = RequestError("prompt too long", 400)
            for rid in ("r0", "r1"):
                info = router.registry.get(rid)
                info.handle = _Flaky(info.handle, budget, exc=err)
            with pytest.raises(RequestError):
                router.complete(dict(self.BODY))
            assert budget["n"] == 3  # one attempt, no retries
            assert obs.FLEET_RETRIES.value() == 0
        finally:
            _close(stacks)

    def test_mid_stream_failover_resumes_byte_identical(self):
        """THE chaos acceptance gate: a replica dies mid-decode (injected
        mid-SSE disconnect); the stream completes on the survivor with no
        error chunk, no gap/duplicate at the seam, and greedy text
        byte-identical to the fault-free run."""
        body = {
            "messages": [{"role": "user", "content": "steady stream"}],
            "max_tokens": 12, "temperature": 0, "stream": True,
        }

        def collect(router):
            chunks = list(router.complete_stream(dict(body)))
            assert all("error" not in c for c in chunks), chunks
            heads = [
                c for c in chunks
                if "role" in c["choices"][0].get("delta", {})
            ]
            finals = [
                c for c in chunks if c["choices"][0].get("finish_reason")
            ]
            assert len(heads) == 1, "role chunk must be emitted exactly once"
            assert len(finals) == 1
            return "".join(
                c["choices"][0]["delta"].get("content") or ""
                for c in chunks
            )

        router, stacks = _fleet(2)
        try:
            reference = collect(router)
            assert reference

            # Same fleet, faults on: the 5th chunk pull dies mid-stream.
            faults.configure("fleet.stream_disconnect@5")
            resumed = collect(router)
            assert resumed == reference
            assert obs.FLEET_FAILOVERS.value() >= 1
            failovers = _flight("failover")
            assert failovers and failovers[-1]["emitted_chars"] > 0
            assert _flight("fault_injected")
            # Zero-post-warmup-compiles invariant holds throughout.
            compiles = [
                e for e in _flight("anomaly")
                if e.get("reason") == "post_warmup_compile"
            ]
            assert not compiles
        finally:
            _close(stacks)

    def test_stream_failover_is_deterministic_under_fixed_spec(self):
        """Same spec, same workload -> same flight-event sequence (the
        reproducibility acceptance criterion, end to end)."""
        body = {
            "messages": [{"role": "user", "content": "replay me"}],
            "max_tokens": 8, "temperature": 0, "stream": True,
        }

        def run_once():
            router, stacks = _fleet(2)
            try:
                faults.configure("fleet.stream_disconnect@4")
                list(router.complete_stream(dict(body)))
                return [
                    (e["point"], e["hit"])
                    for e in _flight("fault_injected")
                ]
            finally:
                _close(stacks)

        first = run_once()
        obs.flight.get_recorder().reset()
        obs.get_registry().reset()
        assert run_once() == first
        assert first == [("fleet.stream_disconnect", 4)]

    def test_hedged_completion_races_a_backup(self):
        router, stacks = _fleet(2, hedge_queue_depth=0)
        try:
            resp = router.complete(dict(self.BODY))
            assert resp["choices"][0]["message"]["content"]
            assert sum(
                obs.FLEET_HEDGES.value(**{"class": c})
                for c in obs.SLO_CLASSES
            ) >= 1
            hedges = _flight("fleet_hedge")
            assert hedges and {
                hedges[-1]["primary"], hedges[-1]["backup"]
            } == {"r0", "r1"}
        finally:
            _close(stacks)


# -- overload shedding --------------------------------------------------------
def _serve_router_on_port(router):
    """Run the router app on a real localhost port; (base_url, stop)."""
    app = build_router_app(router)
    loop = asyncio.new_event_loop()
    runner_box = {}

    async def _start():
        from aiohttp import web

        runner = web.AppRunner(app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", 0)
        await site.start()
        runner_box["runner"] = runner
        runner_box["port"] = runner.addresses[0][1]

    t = threading.Thread(target=loop.run_forever, daemon=True)
    t.start()
    asyncio.run_coroutine_threadsafe(_start(), loop).result(timeout=30)

    def stop():
        async def _stop():
            await runner_box["runner"].cleanup()

        asyncio.run_coroutine_threadsafe(_stop(), loop).result(timeout=30)
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=10)

    return f"http://127.0.0.1:{runner_box['port']}", stop


class TestOverload:
    def test_shed_raises_429_with_retry_after(self):
        router, stacks = _fleet(2, shed_queue_depth=0)
        try:
            with pytest.raises(OverloadError) as ei:
                router.complete({
                    "messages": [{"role": "user", "content": "too much"}],
                    "max_tokens": 4, "temperature": 0,
                })
            assert ei.value.status == 429
            assert ei.value.retry_after_s >= 1
            assert sum(
                obs.FLEET_SHED.value(**{"class": c})
                for c in obs.SLO_CLASSES
            ) == 1
            assert obs.FLEET_REQUESTS.value(outcome="shed") == 1
            assert _flight("request_shed")
        finally:
            _close(stacks)

    def test_forced_route_bypasses_the_shed(self):
        router, stacks = _fleet(2, shed_queue_depth=0)
        try:
            resp = router.complete({
                "messages": [{"role": "user", "content": "operator"}],
                "max_tokens": 4, "temperature": 0,
            }, force_replica="r0")
            # past the shed, on the replica named, to its end (what a
            # random model's 4 tokens decode to is not the router's)
            assert resp["fleet"]["replica"] == "r0"
            assert resp["choices"][0]["finish_reason"] in ("stop", "length")
            assert 1 <= resp["usage"]["completion_tokens"] <= 4
            assert obs.FLEET_REQUESTS.value(outcome="completed") == 1
            assert obs.FLEET_REQUESTS.value(outcome="shed") == 0
            assert not _flight("request_shed")
        finally:
            _close(stacks)

    def test_http_429_retry_after_and_slo_stays_green(self, monkeypatch):
        """Traffic above the watermark gets 429 + Retry-After over HTTP
        while accepted requests' SLO verdict stays green — sheds never
        reach an engine, so the error-rate SLO cannot breach."""
        from opsagent_tpu.cli.slocheck import run_slo_check

        monkeypatch.setenv("OPSAGENT_SLO_TTFT_MS", "60000")
        router, stacks = _fleet(2)
        url, stop = _serve_router_on_port(router)
        try:
            accepted = urllib.request.urlopen(urllib.request.Request(
                url + "/v1/chat/completions",
                data=json.dumps({
                    "messages": [{"role": "user", "content": "admit me"}],
                    "max_tokens": 4, "temperature": 0,
                }).encode(),
                headers={"Content-Type": "application/json"},
            ), timeout=120)
            assert accepted.status == 200

            router.shed_queue_depth = 0  # watermark now below all traffic
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(urllib.request.Request(
                    url + "/v1/chat/completions",
                    data=json.dumps({
                        "messages": [{"role": "user", "content": "surge"}],
                        "max_tokens": 4, "temperature": 0,
                    }).encode(),
                    headers={"Content-Type": "application/json"},
                ), timeout=60)
            assert ei.value.code == 429
            assert int(ei.value.headers["Retry-After"]) >= 1

            # The fleet SLO gate holds: sheds are not engine errors.
            assert run_slo_check(url=url) == 0
            health = json.loads(
                urllib.request.urlopen(url + "/healthz", timeout=30).read()
            )
            assert health["shed_queue_depth"] == 0
            assert set(health["health"]) == {"r0", "r1"}
        finally:
            stop()
            _close(stacks)

    def test_slo_check_passes_while_faults_fire(self, monkeypatch):
        """The fleet-chaos CI gate: seeded faults firing through the
        router, zero failed requests, >= 1 failover, and `opsagent
        slo-check` against the router still exits 0."""
        from opsagent_tpu.cli.slocheck import run_slo_check

        monkeypatch.setenv("OPSAGENT_SLO_TTFT_MS", "60000")
        router, stacks = _fleet(2)
        url, stop = _serve_router_on_port(router)
        try:
            faults.configure("fleet.stream_disconnect@3")
            failed = []
            for i in range(3):
                gen = router.complete_stream({
                    "messages": [
                        {"role": "user", "content": f"chaos smoke {i}"}
                    ],
                    "max_tokens": 6, "temperature": 0, "stream": True,
                })
                chunks = list(gen)
                if any("error" in c for c in chunks):
                    failed.append(i)
            assert not failed
            assert obs.FLEET_FAILOVERS.value() >= 1
            assert run_slo_check(url=url) == 0
        finally:
            stop()
            _close(stacks)


# -- scheduler fault points ---------------------------------------------------
SCHED_CFG = dict(
    model="tiny-test", dtype=jnp.float32, tp=1, page_size=8,
    num_pages=256, max_pages_per_seq=32, max_batch_size=4,
    prefill_buckets=(16,),
)


def _wait_running(sched, timeout=30):
    deadline = time.time() + timeout
    while time.time() < deadline and not sched._running:
        time.sleep(0.01)
    assert sched._running, "request never started decoding"


class TestSchedulerFaults:
    def test_out_of_pages_storm_retries_to_completion(self):
        eng = Engine(EngineConfig(**SCHED_CFG))
        sched = Scheduler(eng)
        sched.start()
        try:
            faults.configure("sched.out_of_pages@1..3")
            req = sched.submit(
                Request([1, 2, 3, 4], SamplingParams(max_tokens=4))
            )
            assert req.done.wait(60), "storm never cleared"
            assert not req.error, req.error
            assert len(req.tokens) >= 1
            assert obs.FAULT_INJECTIONS.value(
                point="sched.out_of_pages"
            ) == 3
        finally:
            sched.stop()

    def test_step_faults_force_engine_restart_and_recovery(self):
        eng = Engine(EngineConfig(**SCHED_CFG))
        sched = Scheduler(
            eng, engine_factory=lambda: Engine(EngineConfig(**SCHED_CFG)),
        )
        sched.start()
        try:
            req = sched.submit(
                Request([5, 6, 7], SamplingParams(max_tokens=6))
            )
            _wait_running(sched)
            # Three consecutive injected tick faults = the loop's
            # persistent-failure threshold -> forced engine restart.
            faults.configure("sched.step_fault@1..3")
            assert req.done.wait(120), "never recovered from step faults"
            assert not req.error, req.error
            assert sched._restarts == 1
            assert req.finish_reason in ("stop", "length")
            assert obs.FAULT_INJECTIONS.value(
                point="sched.step_fault"
            ) == 3
        finally:
            sched.stop()

    def test_requeue_salvaged_resets_admission_clock(self):
        """Satellite: a salvaged re-admission must not double-count its
        queue wait — scheduler.py resets enqueued_s in _requeue_salvaged,
        so a request that already spent (mock) ages in flight is NOT
        admission-timed-out on re-admission, and the re-admission's
        queued goodput phase restarts from the re-queue instant."""
        eng = Engine(EngineConfig(**SCHED_CFG))
        sched = Scheduler(
            eng,
            engine_factory=lambda: Engine(EngineConfig(**SCHED_CFG)),
            admission_timeout_s=5.0,
        )
        sched.start()
        try:
            req = sched.submit(
                Request([9, 8, 7], SamplingParams(max_tokens=6))
            )
            _wait_running(sched)
            # Simulate a request that has been alive far past the
            # admission timeout, then kill the engine under it.
            req.enqueued_s = time.perf_counter() - 600.0
            queued_before = obs.attribution.GOODPUT_SECONDS.value(
                phase="queued"
            )

            def boom(*a, **k):
                raise RuntimeError("device runtime lost")

            sched.engine.step_block = boom
            assert req.done.wait(120), "salvaged request never completed"
            assert not req.error, req.error  # NOT "admission timed out"
            assert sched._restarts == 1
            # The clock was reset: the re-admission's recorded queue wait
            # is the seconds since the re-queue, not the fake 600.
            queued_delta = obs.attribution.GOODPUT_SECONDS.value(
                phase="queued"
            ) - queued_before
            assert queued_delta < 60.0, (
                f"queue wait double-counted: {queued_delta:.1f}s recorded"
            )
        finally:
            sched.stop()

    def test_admission_timeout_reclaims_with_async_pipeline_in_flight(self):
        """Satellite: admission_timeout_s under async_depth=2 — the
        timed-out request reports the timeout while the pipeline is mid-
        flight, and after the batch drains the page pool is exactly
        conserved (nothing leaked by the timed-out admission)."""
        # prefix_cache off: finished sequences must return EVERY page to
        # the allocator, so conservation is an exact equality (the trie
        # would otherwise deliberately retain full prompt pages).
        cfg = dict(
            SCHED_CFG, max_batch_size=1, max_pages_per_seq=40,
            num_pages=64, async_depth=2, prefix_cache=False,
        )
        eng = Engine(EngineConfig(**cfg))
        sched = Scheduler(eng, admission_timeout_s=5.0)
        free0 = eng.alloc.free_pages
        sched.start()
        try:
            # A long-running request occupies the single batch slot with
            # the async lookahead pipeline active.
            req_a = sched.submit(
                Request([1, 2, 3, 4], SamplingParams(max_tokens=64))
            )
            _wait_running(sched)
            # B arrives already past its admission deadline (backdated).
            # While A saturates the batch B just waits; the moment A's
            # slot frees, the admission pass times B out instead of
            # admitting it.
            req_b = Request([5, 6, 7, 8], SamplingParams(max_tokens=4))
            req_b.enqueued_s = time.perf_counter() - 600.0
            sched.submit(req_b)
            assert req_b.done.wait(120), "timed-out request never reported"
            assert "admission timed out" in req_b.error
            assert req_b.seq_id is None  # never admitted, holds no pages
            assert req_a.done.wait(120), "pipelined request never finished"
            assert not req_a.error, req_a.error
            # Page conservation with the pipeline drained.
            deadline = time.time() + 30
            while time.time() < deadline and \
                    eng.alloc.free_pages != free0:
                time.sleep(0.05)
            assert eng.alloc.free_pages == free0
            assert obs.ENGINE_REQUESTS.value(outcome="timeout") == 1
        finally:
            sched.stop()


# -- agent tool fault points --------------------------------------------------
def _tp(thought="", name="", input="", observation="", final=""):
    return json.dumps({
        "question": "q",
        "thought": thought,
        "action": {"name": name, "input": input},
        "observation": observation,
        "final_answer": final,
    })


def _msgs():
    return [
        {"role": "system", "content": "you are a test agent"},
        {"role": "user", "content": "count the pods"},
    ]


class TestToolFaults:
    def test_injected_tool_failure_becomes_observation(
        self, scripted_llm, fake_tools
    ):
        from opsagent_tpu.agent.react import assistant_with_config

        calls = []

        def fake_kubectl(cmd):
            calls.append(cmd)
            return "3 pods"

        fake_tools({"kubectl": fake_kubectl})
        fake = scripted_llm([
            _tp(name="kubectl", input="get pods"),
            _tp(name="kubectl", input="get pods"),
            _tp(observation="3 pods", final="There are 3 pods."),
        ])
        faults.configure("tool.exec@1")
        out, _history = assistant_with_config("fake://m", _msgs())
        assert "There are 3 pods." in out
        # First invocation was injected to fail BEFORE the subprocess
        # ran; the loop fed the failure back as an observation and the
        # model's retry executed for real.
        assert calls == ["get pods"]
        assert obs.FAULT_INJECTIONS.value(point="tool.exec") == 1
        assert obs.TOOL_CALLS.value(tool="kubectl", outcome="error") == 1
        assert obs.TOOL_CALLS.value(tool="kubectl", outcome="ok") == 1
        fed_back = fake.requests[1]["messages"][-1]["content"]
        assert "injected tool subprocess failure" in fed_back

    def test_injected_tool_timeout_becomes_observation(
        self, scripted_llm, fake_tools
    ):
        fake_tools({"kubectl": lambda cmd: "ok"})
        from opsagent_tpu.agent.react import assistant_with_config

        scripted_llm([
            _tp(name="kubectl", input="get ns"),
            _tp(observation="noted", final="Cluster query timed out."),
        ])
        faults.configure("tool.timeout@1")
        out, _ = assistant_with_config("fake://m", _msgs())
        assert "timed out" in out.lower()
        assert obs.FAULT_INJECTIONS.value(point="tool.timeout") == 1
        assert obs.TOOL_CALLS.value(tool="kubectl", outcome="error") == 1


# -- KV transfer fault points -------------------------------------------------
class TestTransferFaults:
    def _records(self):
        import numpy as np

        from opsagent_tpu.serving.fleet.transfer import pack_entries
        from opsagent_tpu.serving.offload.pool import HostPagePool

        pool = HostPagePool(page_size=4, capacity_bytes=1 << 20)
        template = {"k": np.arange(4, dtype=np.float32).reshape(2, 2)}
        pool.put([1, 2, 3, 4], template)
        return pack_entries(pool.entries_for([1, 2, 3, 4])), template

    def test_injected_corruption_rejected_by_digest(self):
        from opsagent_tpu.serving.fleet.transfer import unpack_entries

        records, template = self._records()
        faults.configure("transfer.corrupt@1")
        assert unpack_entries(records, template) == []
        assert obs.FLEET_KV_IMPORT_REJECTS.value() == 1
        rejects = [
            e for e in _flight("anomaly")
            if e.get("reason") == "kv_import_reject"
        ]
        assert rejects and rejects[-1]["cause"] == "digest_mismatch"

    def test_injected_truncation_rejected_by_structure(self):
        from opsagent_tpu.serving.fleet.transfer import unpack_entries

        records, template = self._records()
        faults.configure("transfer.truncate@1")
        assert unpack_entries(records, template) == []
        assert obs.FLEET_KV_IMPORT_REJECTS.value() == 1


# -- heartbeat fault point + backoff ------------------------------------------
class TestHeartbeatContainment:
    def _membership(self):
        import queue as _q

        from opsagent_tpu.serving.fleet.client import FleetMembership

        class _Sched:
            _running: dict = {}
            _waiting: list = []
            _prefilling: dict = {}
            _queue = _q.Queue()

        class _Alloc:
            free_pages = 7

        class _Cfg:
            max_batch_size = 4
            page_size = 8
            tp = 1
            sp = 1
            ep = 1

        class _Eng:
            alloc = _Alloc()
            cfg = _Cfg()

            def prefix_digests(self):
                return []

        class _Stack:
            engine = _Eng()
            scheduler = _Sched()
            model_name = "tiny-test"

        return FleetMembership(
            _Stack(), "http://127.0.0.1:9", "http://127.0.0.1:8",
            replica_id="hb-test", heartbeat_interval_s=0.01,
        )

    def test_registration_failure_backs_off_with_jitter(self):
        from opsagent_tpu.serving.fleet.client import (
            REGISTER_BACKOFF_BASE_S,
            REGISTER_BACKOFF_CAP_S,
        )

        m = self._membership()
        posts = []

        def failing_post(path, body):
            posts.append(path)
            raise urllib.error.URLError("router down")

        m._post = failing_post
        assert not m.register()
        first_backoff = m._register_backoff_s
        assert first_backoff == 2 * REGISTER_BACKOFF_BASE_S
        assert m._next_register_s > time.monotonic()
        assert not m.register()
        # Backoff doubles per failure, capped.
        assert m._register_backoff_s == min(
            REGISTER_BACKOFF_CAP_S, 2 * first_backoff
        )
        assert m._next_register_s > time.monotonic()
        assert posts == ["/fleet/register", "/fleet/register"]

    def test_registration_success_resets_backoff(self):
        m = self._membership()
        m._post = lambda path, body: (_ for _ in ()).throw(
            urllib.error.URLError("down")
        )
        m.register()
        m._post = lambda path, body: {"status": "registered"}
        assert m.register()
        assert m._register_backoff_s == 0.0
        assert m._next_register_s == 0.0

    def test_heartbeat_survives_urlerror_and_drops_are_injected(self):
        m = self._membership()
        posts = []

        def post(path, body):
            posts.append(path)
            if path == "/fleet/heartbeat" and \
                    posts.count("/fleet/heartbeat") == 2:
                raise urllib.error.URLError("blip")
            return {"status": "ok"}

        m._post = post
        faults.configure("client.heartbeat_drop@2")
        m.start()  # registers, then beats every 10 ms
        try:
            deadline = time.time() + 10
            while time.time() < deadline and \
                    posts.count("/fleet/heartbeat") < 4:
                time.sleep(0.02)
        finally:
            m.stop(deregister=False)
        # Loop beat 2 was dropped before the wire (injected); a later
        # wire URLError did not kill the thread or deregister either.
        assert posts[0] == "/fleet/register"
        assert posts.count("/fleet/heartbeat") >= 4
        assert m.registered
        assert m.last_heartbeat_ok is not None
        assert obs.FAULT_INJECTIONS.value(point="client.heartbeat_drop") == 1
