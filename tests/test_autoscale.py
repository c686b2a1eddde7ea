"""Elastic fleet autoscaling (serving/fleet/autoscale): the scaling
policy (shed pressure -> one launch in flight, bounded by max_replicas
and cooldown; sustained idleness -> drain + retire), standby promotion
semantics (role="standby" is unroutable until request-ready), and the
acceptance gate (ISSUE 10): a shed burst launches a standby restored
from an engine snapshot which then serves traffic with no failed
requests."""

import gc
import json
import os

import jax
import jax.numpy as jnp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from opsagent_tpu import obs
from opsagent_tpu.serving.api import ServingStack
from opsagent_tpu.serving.engine import Engine, EngineConfig
from opsagent_tpu.serving.fleet.autoscale import (
    Autoscaler,
    LocalStackLauncher,
    ReplicaLauncher,
)
from opsagent_tpu.serving.fleet.registry import (
    ReplicaInfo,
    ReplicaRegistry,
)
from opsagent_tpu.serving.fleet.router import (
    FleetRouter,
    OverloadError,
    build_router_app,
)

BASE = dict(
    model="tiny-test", dtype=jnp.float32, tp=1, page_size=4,
    num_pages=256, max_pages_per_seq=64, max_batch_size=4,
    prefill_buckets=(16,), decode_block=4, seed=0,
)

CHAT = {
    "messages": [{"role": "user", "content": "hello"}],
    "max_tokens": 4, "temperature": 0,
}


def _router(n=1, **kw):
    """(router, stacks): n in-process decode replicas."""
    router = FleetRouter(**kw)
    stacks = []
    for i in range(n):
        stack = ServingStack(Engine(EngineConfig(**BASE)))
        stacks.append(stack)
        router.add_local(stack, f"r{i}")
    return router, stacks


def _close(stacks):
    for s in stacks:
        s.close()


class FakeLauncher(ReplicaLauncher):
    """Policy-only launcher: registers a standby ReplicaInfo (local with
    no handle, so it is never reaped and never polled) and reports
    request-ready only when told to — so tests control exactly when the
    promote step may fire."""

    def __init__(self, router):
        self.router = router
        self.launched: list[str] = []
        self.stopped: list[str] = []
        self.ready: set[str] = set()

    def launch(self, replica_id: str) -> None:
        self.launched.append(replica_id)
        self.router.registry.register(
            ReplicaInfo(replica_id=replica_id, role="standby", local=True)
        )

    def request_ready(self, replica_id: str) -> bool:
        return replica_id in self.ready

    def stop(self, replica_id: str) -> None:
        self.stopped.append(replica_id)


# -- registry role flips -------------------------------------------------------
class TestSetRole:
    def test_set_role_moves_replica_between_pools(self):
        reg = ReplicaRegistry()
        reg.register(
            ReplicaInfo(replica_id="s", role="standby", local=True)
        )
        assert [i.replica_id for i in reg.alive(role="decode")] == []
        assert reg.set_role("s", "decode")
        assert [i.replica_id for i in reg.alive(role="decode")] == ["s"]
        assert not reg.set_role("ghost", "decode")


# -- scaling policy (no engines involved) --------------------------------------
class TestPolicy:
    def _scaler(self, router, **kw):
        launcher = FakeLauncher(router)
        kw.setdefault("cooldown_s", 0.0)
        return Autoscaler(router, launcher, **kw), launcher

    def test_shed_pressure_launches_one_standby(self):
        router, stacks = _router(1)
        try:
            scaler, launcher = self._scaler(router)
            out = scaler.tick()
            assert out["launched"] is None  # no pressure, no launch
            scaler.note_shed()
            scaler.note_shed()
            out = scaler.tick()
            assert out["launched"] == "scale-1"
            assert launcher.launched == ["scale-1"]
            # The standby is NOT routable yet: route() only considers
            # decode replicas.
            dec = router.registry.alive(role="decode")
            assert [i.replica_id for i in dec] == ["r0"]
            assert obs.FLEET_SCALE_EVENTS.value(direction="up") == 1
        finally:
            _close(stacks)

    def test_one_launch_in_flight_at_a_time(self):
        router, stacks = _router(1)
        try:
            scaler, launcher = self._scaler(router)
            scaler.note_shed()
            assert scaler.tick()["launched"] == "scale-1"
            # Still warming (request_ready False): more shed pressure
            # must not thunder the herd.
            scaler.note_shed()
            out = scaler.tick()
            assert out["launched"] is None and out["promoted"] == []
            # Once ready it is promoted, and only then may another
            # launch happen.
            launcher.ready.add("scale-1")
            scaler.note_shed()
            out = scaler.tick()
            assert out["promoted"] == ["scale-1"]
            assert out["launched"] == "scale-2"
            assert obs.FLEET_SCALE_EVENTS.value(direction="promote") == 1
        finally:
            _close(stacks)

    def test_max_replicas_bounds_the_fleet(self):
        router, stacks = _router(1)
        try:
            scaler, launcher = self._scaler(router, max_replicas=1)
            scaler.note_shed()
            assert scaler.tick()["launched"] == "scale-1"
            launcher.ready.add("scale-1")
            scaler.note_shed()
            out = scaler.tick()
            assert out["promoted"] == ["scale-1"]
            assert out["launched"] is None  # at the bound
        finally:
            _close(stacks)

    def test_cooldown_blocks_back_to_back_launches(self):
        router, stacks = _router(1)
        try:
            scaler, launcher = self._scaler(router, cooldown_s=3600.0)
            scaler.note_shed()
            assert scaler.tick()["launched"] == "scale-1"
            launcher.ready.add("scale-1")
            scaler.note_shed()
            assert scaler.tick()["launched"] is None
        finally:
            _close(stacks)

    def test_snapshot_reports_state(self):
        router, stacks = _router(1)
        try:
            scaler, launcher = self._scaler(router, max_replicas=3)
            scaler.note_shed()
            scaler.tick()
            snap = scaler.snapshot()
            assert snap["pending"] == ["scale-1"]
            assert snap["active"] == []
            assert snap["launched_total"] == 1
            assert snap["max_replicas"] == 3
        finally:
            _close(stacks)


# -- the acceptance gate: shed burst -> snapshot standby serves traffic --------
class TestElasticScaleOut:
    def test_shed_burst_launches_snapshot_standby_no_failed_requests(
        self, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("OPSAGENT_COMPILE_CACHE_MIN_S", "0")
        monkeypatch.setenv(
            "JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache")
        )
        jax.clear_caches()
        router, stacks = _router(1, shed_queue_depth=None)
        snapdir = str(tmp_path / "snap")
        launched_stacks = []

        def factory():
            # What SubprocessLauncher does across a process boundary,
            # in-process: the standby engine comes from the snapshot.
            stack = ServingStack(
                Engine.from_snapshot(snapdir, warmup=False)
            )
            launched_stacks.append(stack)
            return stack

        try:
            stacks[0].engine.snapshot(snapdir)
            scaler = Autoscaler(
                router,
                LocalStackLauncher(router, factory),
                cooldown_s=0.0,
                scale_down_after=2,
            )
            router.autoscaler = scaler  # _check_overload -> note_shed

            # Saturate: watermark 0 means every unforced request sheds.
            router.shed_queue_depth = 0
            with pytest.raises(OverloadError):
                router.complete(dict(CHAT))
            assert sum(
                obs.FLEET_SHED.value(**{"class": c})
                for c in obs.SLO_CLASSES
            ) == 1

            out = scaler.tick()
            assert out["launched"] == "scale-1"
            out = scaler.tick()
            assert out["promoted"] == ["scale-1"]
            ids = {
                i.replica_id
                for i in router.registry.alive(role="decode")
            }
            assert ids == {"r0", "scale-1"}

            # Burst over, watermark back up: traffic flows and every
            # request succeeds — including on the promoted standby.
            router.shed_queue_depth = None
            for _ in range(3):
                resp = router.complete(dict(CHAT))
                assert resp["choices"][0]["message"]["content"]
            forced = router.complete(
                dict(CHAT), force_replica="scale-1"
            )
            assert forced["choices"][0]["message"]["content"]
            assert obs.FLEET_REQUESTS.value(outcome="error") == 0

            # Pressure gone + idle: the standby is drained (graceful)
            # and retired, and the original replica remains.
            retired = []
            for _ in range(4):
                retired += scaler.tick()["retired"]
            assert retired == ["scale-1"]
            ids = {
                i.replica_id
                for i in router.registry.alive(role="decode")
            }
            assert ids == {"r0"}
            # Exactly one standby was ever built, and it came from the
            # snapshot restore path.
            assert len(launched_stacks) == 1
            assert launched_stacks[0].engine.init_stats[
                "restore_source"
            ] == os.path.abspath(snapdir)
            assert obs.FLEET_SCALE_EVENTS.value(direction="down") == 1
        finally:
            _close(stacks)
            gc.collect()


# -- router healthz exposes the scaler -----------------------------------------
class TestHealthzAutoscale:
    def test_router_healthz_carries_autoscale_block(self):
        import asyncio

        router, stacks = _router(1)
        try:
            scaler = Autoscaler(router, FakeLauncher(router))
            router.autoscaler = scaler
            scaler.note_shed()
            app = build_router_app(router)

            async def _get():
                client = TestClient(TestServer(app))
                await client.start_server()
                try:
                    resp = await client.get("/healthz")
                    return json.loads(await resp.text())
                finally:
                    await client.close()

            body = asyncio.new_event_loop().run_until_complete(_get())
            auto = body["autoscale"]
            assert auto["shed_pending"] == 1
            assert auto["active"] == []
            assert auto["max_replicas"] == 4
        finally:
            _close(stacks)
