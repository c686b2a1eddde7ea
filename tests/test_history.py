"""Telemetry time machine (ISSUE 18): TelemetryHistory's tiered rings
(counter deltas, rollup conservation, byte bound, query/rebucket, rate),
the /api/metrics/history endpoints (replica handler + fleet-aggregated
router view with {replica_id}: prefixes and skew-corrected timestamps),
the SLO watchdog's history-backed decode rate + per-class report, the
``slo-check --class`` gate, tail-based trace retention (the p=0.01
acceptance criterion: every breached/errored/failed-over request still
answers /api/timeline/{id}), the anomaly dump's appended history block,
and the ``opsagent top`` cockpit rendering >=3 frames against a live
2-replica fleet."""

import asyncio
import io
import json
import threading
import urllib.request

import jax.numpy as jnp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from opsagent_tpu import obs
from opsagent_tpu.cli.slocheck import _check_class
from opsagent_tpu.cli.top import run_top, sparkline
from opsagent_tpu.obs.history import (
    POINT_BYTES,
    TIER_SPECS,
    TelemetryHistory,
    parse_query,
)
from opsagent_tpu.serving import faults
from opsagent_tpu.serving.api import ServingStack
from opsagent_tpu.serving.engine import Engine, EngineConfig
from opsagent_tpu.serving.fleet.registry import ReplicaInfo
from opsagent_tpu.serving.fleet.router import FleetRouter, build_router_app

BASE = dict(
    model="tiny-test", dtype=jnp.float32, tp=1, page_size=4,
    num_pages=256, max_pages_per_seq=64, max_batch_size=4,
    prefill_buckets=(16, 32, 64), decode_block=4, seed=0,
)

CHAT = {
    "messages": [{"role": "user", "content": "hello"}],
    "max_tokens": 4, "temperature": 0,
}

T0 = 1_700_000_000.0


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def _fleet(n=2):
    router = FleetRouter()
    stacks = []
    for i in range(n):
        stack = ServingStack(Engine(EngineConfig(**BASE)))
        stacks.append(stack)
        router.add_local(stack, f"r{i}")
    return router, stacks


def _close(stacks):
    for s in stacks:
        s.close()


def _serve_router_on_port(router):
    """Run the router app on a real localhost port (urllib cannot talk
    to aiohttp's TestClient transport). Returns (base_url, stop_fn)."""
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


def _counter(total_box):
    """A counter reader driven by mutating total_box["v"]."""
    return lambda: total_box["v"]


# -- the store itself (synthetic clock, no engines) ---------------------------
class TestTelemetryHistory:
    def test_counter_records_deltas_not_totals(self):
        h = TelemetryHistory(max_bytes=1 << 20, interval_s=1.0)
        box = {"v": 100.0}
        h.register("tokens", "counter", _counter(box))
        h.sample(now=T0)          # first sweep: baseline only, no point
        box["v"] = 105.0
        h.sample(now=T0 + 1)
        box["v"] = 112.0
        h.sample(now=T0 + 2)
        pts = h.query(series=["tokens"], since=60.0, now=T0 + 2)[
            "series"]["tokens"]["points"]
        assert [p[1] for p in pts] == [5.0, 7.0]
        assert [p[0] for p in pts] == [T0 + 1, T0 + 2]

    def test_counter_reset_clamps_to_zero_delta(self):
        h = TelemetryHistory(max_bytes=1 << 20)
        box = {"v": 50.0}
        h.register("tokens", "counter", _counter(box))
        h.sample(now=T0)
        box["v"] = 3.0            # process restart: total went backwards
        h.sample(now=T0 + 1)
        pts = h.query(series=["tokens"], since=60.0, now=T0 + 1)[
            "series"]["tokens"]["points"]
        assert [p[1] for p in pts] == [0.0]

    def test_rollup_conserves_counter_sum_across_all_tiers(self):
        """70 min of 1 Hz sweeps at +7 tokens each populates all three
        tiers; summing every surviving delta still equals exactly what
        the counter advanced by — rollup aggregates, never loses."""
        h = TelemetryHistory(max_bytes=8 << 20, interval_s=1.0)
        box = {"v": 0.0}
        h.register("tokens", "counter", _counter(box))
        n = 70 * 60
        for i in range(n):
            box["v"] += 7.0
            h.sample(now=T0 + i)
        per_tier = h.stats()["points_per_tier"]
        assert per_tier[1] > 0 and per_tier[2] > 0, per_tier
        # Tier 0 holds only its 300 s horizon (plus rollup slack).
        assert per_tier[0] <= 2 * (TIER_SPECS[0][1] + TIER_SPECS[1][0])
        pts = h.query(series=["tokens"], since=n + 10, now=T0 + n - 1)[
            "series"]["tokens"]["points"]
        total = sum(p[1] for p in pts)
        assert abs(total - 7.0 * (n - 1)) < 1e-6  # first sweep = baseline

    def test_step_rebucket_is_exact_for_counters(self):
        h = TelemetryHistory(max_bytes=8 << 20)
        box = {"v": 0.0}
        h.register("tokens", "counter", _counter(box))
        n = 600
        for i in range(n):
            box["v"] += 7.0
            h.sample(now=T0 + i)
        pts = h.query(
            series=["tokens"], since=n + 10, step=60.0, now=T0 + n - 1,
        )["series"]["tokens"]["points"]
        # Interior buckets each cover 60 full sweeps of +7.
        assert pts[2:-2]
        assert all(p[1] == 60 * 7.0 for p in pts[2:-2]), pts

    def test_gauge_rebucket_averages(self):
        h = TelemetryHistory(max_bytes=1 << 20)
        vals = iter([2.0, 4.0, 6.0, 8.0])
        h.register("occ", "gauge", lambda: next(vals))
        for i in range(4):
            h.sample(now=T0 + i)
        pts = h.query(
            series=["occ"], since=60.0, step=10.0, now=T0 + 3,
        )["series"]["occ"]["points"]
        assert len(pts) == 1 and pts[0][1] == pytest.approx(5.0)

    def test_byte_budget_evicts_oldest_but_never_overruns(self):
        h = TelemetryHistory(max_bytes=4096)
        box = {"v": 0.0}
        h.register("tokens", "counter", _counter(box))
        h.register("occ", "gauge", lambda: 1.0)
        for i in range(2000):
            box["v"] += 1.0
            h.sample(now=T0 + i)
        st = h.stats()
        assert st["evicted"] > 0
        assert st["bytes"] <= st["max_bytes"] == 4096
        assert st["bytes"] == sum(st["points_per_tier"]) * POINT_BYTES
        # The NEWEST points survive eviction.
        pts = h.query(series=["tokens"], since=10.0, now=T0 + 1999)[
            "series"]["tokens"]["points"]
        assert pts and pts[-1][0] == T0 + 1999

    def test_rate_and_window_sum(self):
        h = TelemetryHistory(max_bytes=1 << 20)
        box = {"v": 0.0}
        h.register("tokens", "counter", _counter(box))
        h.sample(now=T0)
        assert h.rate("tokens", 60.0, now=T0) is None  # no points yet
        for i in range(1, 11):
            box["v"] += 5.0
            h.sample(now=T0 + i)
        assert h.rate("tokens", 60.0, now=T0 + 10) == pytest.approx(5.0)
        assert h.window_sum("tokens", 60.0, now=T0 + 10) == 50.0
        assert h.window_sum("tokens", 3.5, now=T0 + 10) == 20.0
        assert h.rate("ghost", 60.0, now=T0 + 10) is None
        assert h.window_sum("ghost", 60.0, now=T0 + 10) == 0.0

    def test_query_since_filters_and_register_is_idempotent(self):
        h = TelemetryHistory(max_bytes=1 << 20)
        box = {"v": 0.0}
        h.register("tokens", "counter", _counter(box))
        for i in range(20):
            box["v"] += 1.0
            h.sample(now=T0 + i)
        # Re-registering keeps the ring (modules reload across tests).
        h.register("tokens", "counter", _counter(box))
        recent = h.query(series=["tokens"], since=5.0, now=T0 + 19)[
            "series"]["tokens"]["points"]
        assert len(recent) == 6  # t in [14 .. 19]
        out = h.query(series=["tokens", "ghost"], since=60.0, now=T0 + 19)
        assert list(out["series"]) == ["tokens"]
        assert out["tiers"][0] == {"step_s": 1.0, "horizon_s": 300.0}

    def test_parse_query_grammar(self):
        kw = parse_query({"series": "a, b,", "since": "60", "step": "10"})
        assert kw == {"series": ["a", "b"], "since": 60.0, "step": 10.0}
        assert parse_query({}) == {}
        with pytest.raises(ValueError):
            parse_query({"since": "banana"})
        with pytest.raises(ValueError):
            parse_query({"step": "x"})

    def test_reader_failure_skips_series_not_the_sweep(self):
        h = TelemetryHistory(max_bytes=1 << 20)

        def boom():
            raise RuntimeError("reader died")

        h.register("bad", "gauge", boom)
        h.register("good", "gauge", lambda: 1.0)
        h.sample(now=T0)
        out = h.query(since=60.0, now=T0)["series"]
        assert out["good"]["points"] and not out["bad"]["points"]


# -- watchdog decode rate + per-class report (satellite 1) --------------------
class TestWatchdogHistoryIntegration:
    def test_decode_rate_rides_the_history_sampler(self):
        import time as _time

        h = obs.history.get_history()
        now = _time.time()
        h.sample(now=now - 2)            # baseline sweep
        obs.DECODE_TOKENS.inc(50)
        h.sample(now=now - 1)
        obs.DECODE_TOKENS.inc(70)
        h.sample(now=now)
        rate = obs.slo.get_watchdog()._decode_rate()
        assert rate == pytest.approx(70.0, rel=0.05)

    def test_class_report_windows_attainment_and_burn(self):
        import time as _time

        h = obs.history.get_history()
        now = _time.time()
        h.sample(now=now - 2)
        for _ in range(9):
            obs.CLASS_REQUESTS.inc(
                **{"class": "interactive", "outcome": "completed"}
            )
        obs.CLASS_REQUESTS.inc(
            **{"class": "interactive", "outcome": "error"}
        )
        obs.CLASS_TTFT_SECONDS.observe(0.05, **{"class": "interactive"})
        h.sample(now=now - 1)
        h.sample(now=now)
        rows = obs.slo.get_watchdog().class_report()
        assert [r["class"] for r in rows] == ["interactive"]
        r = rows[0]
        assert r["requests"] == 10 and r["bad"] == 1
        assert r["attainment"] == pytest.approx(0.9)
        assert r["ttft_p95_ms"] is not None
        w5 = r["windows"]["5m"]
        assert w5["requests"] == 10
        # (1 - 0.9) / 0.01 budget = 10x burn.
        assert w5["burn_rate"] == pytest.approx(10.0)
        full = obs.slo.evaluate()
        assert full["classes"] == rows or full["classes"]
        assert full["error_budget"] == pytest.approx(0.01)

    def test_slo_check_class_gate_exit_codes(self, capsys):
        healthy = {
            "error_budget": 0.01,
            "classes": [{
                "class": "interactive", "requests": 100,
                "attainment": 0.995,
                "windows": {"5m": {
                    "requests": 100, "attainment": 0.995, "burn_rate": 0.5,
                }},
            }],
        }
        assert _check_class(healthy, "interactive") == 0
        burning = {
            "error_budget": 0.01,
            "classes": [{
                "class": "batch", "requests": 40, "attainment": 0.999,
                "windows": {"5m": {
                    "requests": 40, "attainment": 0.9, "burn_rate": 10.0,
                }},
            }],
        }
        assert _check_class(burning, "batch") == 1
        low_attainment = {
            "error_budget": 0.01,
            "classes": [{
                "class": "batch", "requests": 40, "attainment": 0.5,
                "windows": {},
            }],
        }
        assert _check_class(low_attainment, "batch") == 1
        assert _check_class({"classes": []}, "background") == 2
        capsys.readouterr()


# -- endpoints: replica handler, router passthrough, fleet aggregation --------
class TestHistoryEndpoints:
    def test_router_endpoint_serves_history_and_rejects_bad_query(self):
        router, stacks = _fleet(1)
        app = build_router_app(router)

        async def scenario():
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                obs.history.get_history().sample()
                r = await client.get(
                    "/api/metrics/history?since=60&step=10"
                )
                assert r.status == 200
                body = await r.json()
                assert "decode_tokens" in body["series"]
                assert body["tiers"][0]["step_s"] == 1.0
                assert body["replicas"] == ["r0"]
                r = await client.get("/api/metrics/history?since=banana")
                assert r.status == 400
                assert "error" in await r.json()
            finally:
                await client.close()

        try:
            run(scenario())
        finally:
            _close(stacks)

    def test_server_handler_parses_the_same_grammar(self):
        """The per-replica server handler shares parse_query with the
        router — same 400 on the same malformed input."""
        from opsagent_tpu.server import handlers

        class _Req:
            def __init__(self, q):
                self.query = q

        async def scenario():
            obs.history.get_history().sample()
            ok = await handlers.history_get(_Req({"since": "60"}))
            assert ok.status == 200
            assert "series" in json.loads(ok.text)
            bad = await handlers.history_get(_Req({"step": "banana"}))
            assert bad.status == 400

        run(scenario())

    def test_fleet_aggregation_prefixes_and_skew_corrects_remote_series(
        self, monkeypatch
    ):
        """Remote replica series come back {replica_id}:{name} with
        timestamps shifted by -offset into the router's clock; local
        series stay unprefixed (in-process replicas share the router's
        store)."""

        class StubRemote:
            def history(self, series=None, since=300.0, step=None):
                return {"series": {
                    "decode_tokens": {
                        "kind": "counter",
                        "points": [[T0 + 5.0, 7.0], [T0 + 6.0, 7.0]],
                    },
                }}

        router, stacks = _fleet(1)
        try:
            info = ReplicaInfo(replica_id="rr", url="http://fake")
            info.handle = StubRemote()
            router.registry.register(info)
            monkeypatch.setattr(
                router.registry, "clock_offsets",
                lambda: {"rr": 2.0, "r0": 0.0},
            )
            obs.history.get_history().sample()
            out = router.metrics_history(since=600.0)
            assert set(out["replicas"]) == {"r0", "rr"}
            assert "decode_tokens" in out["series"]          # local, bare
            remote = out["series"]["rr:decode_tokens"]
            assert remote["kind"] == "counter"
            # replica wall 2 s ahead -> shifted back into router time.
            assert [p[0] for p in remote["points"]] == [T0 + 3.0, T0 + 4.0]
            assert out["clock_offset_s"]["rr"] == 2.0
        finally:
            _close(stacks)

    def test_slo_aggregate_merges_remote_class_reports(self):
        """A real HTTP fleet classifies completions in the replica
        processes: the router's /api/slo folds those per-replica class
        reports into one fleet view (sums, recomputed attainment,
        worst-replica p95, request-weighted windows)."""
        from opsagent_tpu.serving.fleet.router import _merge_class_reports

        local = [{
            "class": "interactive", "requests": 10, "bad": 1,
            "attainment": 0.9, "ttft_p95_ms": 100.0, "itl_p95_ms": None,
            "outcomes": {"completed": 9, "error": 1},
            "windows": {"5m": {
                "requests": 10, "attainment": 0.9, "burn_rate": 10.0,
            }},
        }]
        remote = [{
            "class": "interactive", "requests": 30, "bad": 0,
            "attainment": 1.0, "ttft_p95_ms": 250.0, "itl_p95_ms": 40.0,
            "outcomes": {"completed": 30},
            "windows": {"5m": {
                "requests": 30, "attainment": 1.0, "burn_rate": 0.0,
            }},
        }, {
            "class": "batch", "requests": 5, "bad": 0,
            "attainment": 1.0, "ttft_p95_ms": None, "itl_p95_ms": None,
            "outcomes": {"completed": 5}, "windows": {},
        }]
        rows = _merge_class_reports([local, remote], budget=0.01)
        assert [r["class"] for r in rows] == ["interactive", "batch"]
        inter = rows[0]
        assert inter["requests"] == 40 and inter["bad"] == 1
        assert inter["attainment"] == pytest.approx(39 / 40)
        assert inter["ttft_p95_ms"] == 250.0   # worst replica
        assert inter["itl_p95_ms"] == 40.0
        assert inter["outcomes"] == {"completed": 39, "error": 1}
        w5 = inter["windows"]["5m"]
        assert w5["requests"] == 40
        assert w5["attainment"] == pytest.approx(0.975)
        assert w5["burn_rate"] == pytest.approx(2.5)
        assert _merge_class_reports([[], []], 0.01) == []

    def test_aggregation_degrades_when_a_remote_fails(self, monkeypatch):
        class DeadRemote:
            def history(self, **kw):
                raise OSError("connection refused")

        router, stacks = _fleet(1)
        try:
            info = ReplicaInfo(replica_id="dead", url="http://fake")
            info.handle = DeadRemote()
            router.registry.register(info)
            out = router.metrics_history(since=60.0)
            assert "dead" in out["replicas"]
            assert not any(k.startswith("dead:") for k in out["series"])
        finally:
            _close(stacks)


# -- tail-based retention: the p=0.01 acceptance criterion --------------------
class TestTailRetention:
    def test_anomalous_requests_always_answer_timeline_at_p001(
        self, tmp_path, monkeypatch
    ):
        """Forced load at trace-sample p=0.01: healthy requests are
        (mostly) dropped, yet 100% of breached / errored / failed-over
        requests still return a full /api/timeline/{id} over HTTP."""
        monkeypatch.setenv("OPSAGENT_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("OPSAGENT_SLO_TTFT_MS", "60000")
        obs.trace.set_sample_probability(0.01)
        router, stacks = _fleet(2)
        url, stop = _serve_router_on_port(router)
        anomalous_ids = []
        try:
            # Phase 1 — healthy traffic: nothing breaches, so retention
            # is a pure p=0.01 draw and almost everything is dropped.
            for _ in range(25):
                resp = router.complete(dict(CHAT))
                assert resp["choices"][0]["message"]["content"]
            dropped = obs.TRACE_RETENTION.value(decision="dropped")
            assert dropped > 0, "p=0.01 must shed healthy traces"

            # Phase 2a — TTFT breach: every request now blows the SLO
            # and its anomaly event pins the trace.
            monkeypatch.setenv("OPSAGENT_SLO_TTFT_MS", "0.0001")
            for _ in range(3):
                resp = router.complete(dict(CHAT))
                anomalous_ids.append(resp["id"])
            monkeypatch.setenv("OPSAGENT_SLO_TTFT_MS", "60000")

            # Phase 2b — mid-stream failover: the journey is marked
            # anomalous on the resume path. The SECOND chunk pull dies:
            # every stream has a role chunk and a final one, while how many
            # lie between is the random model's (its 12 tokens decode to
            # one or two printable deltas; a later pull may never come).
            faults.configure("fleet.stream_disconnect@2")
            try:
                chunks = list(router.complete_stream({
                    "messages": [
                        {"role": "user", "content": "failover me"}
                    ],
                    "max_tokens": 12, "temperature": 0, "stream": True,
                }))
            finally:
                faults.reset()
            assert all("error" not in c for c in chunks)
            anomalous_ids.append(chunks[0]["id"])
            assert obs.FLEET_FAILOVERS.value() >= 1

            kept = obs.TRACE_RETENTION.value(decision="kept_anomalous")
            assert kept >= len(anomalous_ids)

            # The criterion: every anomalous id answers over HTTP.
            for rid in anomalous_ids:
                with urllib.request.urlopen(
                    f"{url}/api/timeline/{rid}", timeout=10
                ) as r:
                    assert r.status == 200
                    tl = json.loads(r.read().decode())
                assert tl.get("request_id") == rid or tl.get("trace")
        finally:
            stop()
            _close(stacks)
            obs.trace.set_sample_probability(None)

    def test_anomaly_dump_carries_the_history_leadup(
        self, tmp_path, monkeypatch
    ):
        """Satellite 2: the flight dump written on a breach appends a
        {"kind": "history"} block — the last 60 s of every series —
        so postmortems need no live scrape."""
        import time as _time

        monkeypatch.setenv("OPSAGENT_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("OPSAGENT_SLO_TTFT_MS", "0.0001")
        h = obs.history.get_history()
        now = _time.time()
        h.sample(now=now - 2)
        obs.DECODE_TOKENS.inc(11)
        h.sample(now=now - 1)
        obs.DECODE_TOKENS.inc(13)
        h.sample(now=now)
        router, stacks = _fleet(1)
        try:
            router.complete(dict(CHAT))  # breaches -> anomaly -> dump
        finally:
            _close(stacks)
        dumps = sorted(tmp_path.glob("flight-*.jsonl"))
        assert dumps, "breach must dump the flight ring"
        blocks = []
        for p in dumps:
            for line in p.read_text().splitlines():
                d = json.loads(line)
                if d.get("kind") == "history":
                    blocks.append(d)
        assert blocks, "anomaly dump must append the history block"
        pts = blocks[-1]["series"]["decode_tokens"]["points"]
        assert sum(p[1] for p in pts) == pytest.approx(24.0)


# -- the cockpit: opsagent top against a live fleet ---------------------------
class TestTopCockpit:
    def test_sparkline_shapes(self):
        assert sparkline([], width=8) == "·" * 8
        line = sparkline([[float(i), float(i)] for i in range(24)], width=8)
        assert len(line) == 8
        assert line[0] <= line[-1]  # ramp renders as a ramp

    def test_top_renders_three_frames_against_a_live_fleet(
        self, tmp_path, monkeypatch
    ):
        """The acceptance gate: >=3 consecutive frames from a live
        in-process 2-replica fleet over real HTTP (no TTY), showing
        per-replica health and per-class SLO rows."""
        monkeypatch.setenv("OPSAGENT_FLIGHT_DIR", str(tmp_path))
        monkeypatch.setenv("OPSAGENT_SLO_TTFT_MS", "60000")
        router, stacks = _fleet(2)
        url, stop = _serve_router_on_port(router)
        try:
            for _ in range(2):
                resp = router.complete(dict(CHAT))
                assert resp["choices"][0]["message"]["content"]
            obs.history.get_history().sample()
            buf = io.StringIO()
            rc = run_top(
                url, interval_s=0.05, frames=3, out=buf, color=False,
            )
            out = buf.getvalue()
            assert rc == 0
            assert out.count("opsagent top") == 3
            assert out.count("-" * 72) == 2  # non-TTY frame separator
            assert "\x1b[" not in out        # color=False: no ANSI
            assert "r0" in out and "r1" in out
            assert "healthy" in out
            assert "interactive" in out      # per-class SLO row
            assert "slo classes" in out and "anomaly tail" in out
        finally:
            stop()
            _close(stacks)

    def test_top_returns_one_when_nothing_answers(self):
        buf = io.StringIO()
        rc = run_top(
            "http://127.0.0.1:9",  # discard port: nothing listens
            interval_s=0.01, frames=2, out=buf, color=False,
        )
        assert rc == 1
        assert "opsagent top" in buf.getvalue()  # frames still render
