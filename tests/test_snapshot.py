"""Engine snapshot/restore subsystem (serving/snapshot): manifest
integrity, fingerprint refusal, the mmap restore path, and the
acceptance gates (ISSUE 10): a restored engine reaches request-ready
with ZERO post-warmup compiles and produces byte-identical greedy
output vs the fresh-init engine it was captured from — in fp and
int8-KV configs (plus the int8-weights config, which exercises the
already-quantized restore path: restore must apply quantize SPECS
without re-quantizing the leaves)."""

import gc
import json
import os

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
from aiohttp.test_utils import TestClient, TestServer

from opsagent_tpu import obs
from opsagent_tpu.serving.engine import (
    Engine,
    EngineConfig,
    enable_compilation_cache,
)
from opsagent_tpu.serving.sampler import SamplingParams
from opsagent_tpu.serving.snapshot import (
    MANIFEST_NAME,
    SnapshotError,
    read_manifest,
    verify_snapshot,
)
from opsagent_tpu.serving.snapshot.manifest import write_manifest

BASE = dict(
    model="tiny-test", dtype=jnp.float32, tp=1, page_size=4,
    num_pages=256, max_pages_per_seq=64, max_batch_size=4,
    prefill_buckets=(16,), decode_block=4, seed=0,
)

PROMPTS = [list(range(1, 13)), list(range(40, 54))]
GREEDY = SamplingParams(temperature=0.0, max_tokens=8)


@pytest.fixture()
def cache_env(tmp_path, monkeypatch):
    """Isolated persistent compile cache + zero min-compile threshold,
    so every warmed program lands in the snapshot's cache artifact."""
    monkeypatch.setenv("OPSAGENT_COMPILE_CACHE_MIN_S", "0")
    monkeypatch.setenv(
        "JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache-fresh")
    )
    # Earlier tests' in-process executables would otherwise let this
    # test's writer engine skip compiles entirely, leaving its isolated
    # persistent cache dir empty (snapshot then packages 0 entries).
    jax.clear_caches()
    return tmp_path


def _snap(tmp_path, **overrides):
    """(engine, snapshot_dir, manifest): warmed engine captured."""
    eng = Engine(EngineConfig(**{**BASE, **overrides}))
    eng.warmup("bench")
    snapdir = str(tmp_path / "snap")
    man = eng.snapshot(snapdir)
    return eng, snapdir, man


def _teardown_and_restore(eng, snapdir, tmp_path, monkeypatch, warmup):
    """Drop the writer engine (and the in-process executable caches, so
    the restore cannot coast on them), then restore into a second cache
    dir holding only what the snapshot packaged."""
    del eng
    gc.collect()
    jax.clear_caches()
    monkeypatch.setenv(
        "JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache-restore")
    )
    return Engine.from_snapshot(snapdir, warmup=warmup)


# -- manifest / verify ---------------------------------------------------------
class TestWriteVerify:
    def test_roundtrip_manifest_and_verify(self, tmp_path, cache_env):
        eng, snapdir, man = _snap(tmp_path)
        assert man["format"] == 1
        assert man["engine"]["page_size"] == BASE["page_size"]
        assert man["model"]["vocab_size"] == eng.model_cfg.vocab_size
        assert len(man["leaves"]) == len(
            jax.tree_util.tree_leaves(eng.params)
        )
        # Warmed under MIN_S=0: the compile cache artifact is non-empty.
        assert man["compile_cache"]["entries"] > 0
        assert man["kv_plan"]["num_pages"] == BASE["num_pages"]
        rep = verify_snapshot(snapdir)
        assert rep["ok"] and not rep["errors"]
        assert rep["fingerprint"] == man["fingerprint"]
        quick = verify_snapshot(snapdir, quick=True)
        assert quick["ok"]
        assert obs.SNAPSHOT_OPS.value(op="write") == 1

    def test_verify_catches_flipped_leaf_byte(self, tmp_path, cache_env):
        _eng, snapdir, man = _snap(tmp_path)
        fpath = os.path.join(snapdir, man["leaves"][3]["file"])
        with open(fpath, "r+b") as f:
            b = f.read(1)
            f.seek(0)
            f.write(bytes([b[0] ^ 0xFF]))
        rep = verify_snapshot(snapdir)
        assert not rep["ok"]
        assert any("digest" in e for e in rep["errors"])
        # Quick mode skips content digests, so the flip slips through —
        # that is the documented tradeoff, pinned here.
        assert verify_snapshot(snapdir, quick=True)["ok"]

    def test_verify_catches_edited_config(self, tmp_path, cache_env):
        _eng, snapdir, man = _snap(tmp_path)
        man["engine"]["page_size"] = 8
        write_manifest(snapdir, man)
        rep = verify_snapshot(snapdir)
        assert not rep["ok"]
        assert not rep["fingerprint_ok"]

    def test_missing_manifest_is_unreadable(self, tmp_path):
        with pytest.raises(SnapshotError):
            read_manifest(str(tmp_path))


# -- restore -------------------------------------------------------------------
class TestRestore:
    def test_restore_byte_identical_zero_compiles(
        self, tmp_path, cache_env, monkeypatch
    ):
        eng, snapdir, _man = _snap(tmp_path)
        fresh = eng.generate(PROMPTS, GREEDY)
        eng2 = _teardown_and_restore(
            eng, snapdir, tmp_path, monkeypatch, warmup="bench"
        )
        assert eng2.init_stats["restore_source"] == os.path.abspath(snapdir)
        assert eng2.init_stats["compile_cache_preseeded"] > 0
        # Request-ready means serving compiles NOTHING: the gauge must
        # not move across a full admission + decode.
        gauge0 = obs.POST_WARMUP_COMPILES.value()
        restored = eng2.generate(PROMPTS, GREEDY)
        assert obs.POST_WARMUP_COMPILES.value() == gauge0
        assert restored == fresh
        assert obs.SNAPSHOT_OPS.value(op="restore") == 1

    def test_restore_int8_kv_identical_zero_compiles(
        self, tmp_path, cache_env, monkeypatch
    ):
        eng, snapdir, man = _snap(tmp_path, kv_quantize="int8")
        assert man["engine"]["kv_quantize"] == "int8"
        fresh = eng.generate(PROMPTS, GREEDY)
        eng2 = _teardown_and_restore(
            eng, snapdir, tmp_path, monkeypatch, warmup="bench"
        )
        gauge0 = obs.POST_WARMUP_COMPILES.value()
        restored = eng2.generate(PROMPTS, GREEDY)
        assert obs.POST_WARMUP_COMPILES.value() == gauge0
        assert restored == fresh

    def test_restore_int8_weights_not_double_quantized(
        self, tmp_path, cache_env, monkeypatch
    ):
        # The quantized engine snapshots ALREADY-quantized leaves (q +
        # scale per linear); restore must rebuild quantize SPECS for the
        # sharding but never run quantize_params again — double
        # quantization would silently corrupt every weight.
        from opsagent_tpu.serving.snapshot.writer import spec_leaf_paths

        eng, snapdir, man = _snap(tmp_path, quantize="int8")
        n_fp_leaves = len(spec_leaf_paths(eng.model_cfg, ""))
        assert len(man["leaves"]) > n_fp_leaves  # q + scale leaves
        fresh = eng.generate(PROMPTS, GREEDY)
        eng2 = _teardown_and_restore(
            eng, snapdir, tmp_path, monkeypatch, warmup="bench"
        )
        restored = eng2.generate(PROMPTS, GREEDY)
        assert restored == fresh

    def test_fingerprint_mismatch_refused(
        self, tmp_path, cache_env, monkeypatch
    ):
        eng, snapdir, man = _snap(tmp_path)
        man["engine"]["page_size"] = 8  # config edit after capture
        write_manifest(snapdir, man)
        del eng
        gc.collect()
        with pytest.raises(SnapshotError, match="fingerprint"):
            Engine.from_snapshot(snapdir)
        assert obs.SNAPSHOT_OPS.value(op="refused") == 1

    def test_device_count_mismatch_refused(
        self, tmp_path, cache_env, monkeypatch
    ):
        from opsagent_tpu.serving.snapshot.manifest import fingerprint

        _eng, snapdir, man = _snap(tmp_path)
        # Relative to whatever the host really has (conftest forces 8
        # CPU devices) so the claim is guaranteed to mismatch.
        man["jax"]["n_devices"] = len(jax.devices()) + 1
        man["fingerprint"] = fingerprint(man["model"], man["engine"])
        write_manifest(snapdir, man)
        with pytest.raises(SnapshotError, match="devices"):
            Engine.from_snapshot(snapdir)

    def test_leaf_order_drift_refused(
        self, tmp_path, cache_env, monkeypatch
    ):
        _eng, snapdir, man = _snap(tmp_path)
        man["leaves"][0], man["leaves"][1] = (
            man["leaves"][1], man["leaves"][0],
        )
        write_manifest(snapdir, man)
        with pytest.raises(SnapshotError, match="leaf order"):
            Engine.from_snapshot(snapdir)

    def test_truncated_leaf_refused(self, tmp_path, cache_env):
        _eng, snapdir, man = _snap(tmp_path)
        fpath = os.path.join(snapdir, man["leaves"][0]["file"])
        with open(fpath, "r+b") as f:
            f.truncate(os.path.getsize(fpath) - 4)
        with pytest.raises(SnapshotError, match="truncated|bytes"):
            Engine.from_snapshot(snapdir)


# -- env / compile-cache wiring ------------------------------------------------
class TestCompileCacheEnv:
    def test_jax_dir_env_is_the_location(self, tmp_path, monkeypatch):
        target = str(tmp_path / "cc")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", target)
        assert enable_compilation_cache() == target
        assert jax.config.jax_compilation_cache_dir == target

    def test_repo_spellings_are_gone(self, tmp_path, monkeypatch):
        """The repo's own variables no longer move the cache: unset
        JAX_COMPILATION_CACHE_DIR means the fixed in-checkout path."""
        from opsagent_tpu.serving.engine import compile_cache_dir

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("OPSAGENT_COMPILE_CACHE_DIR", str(tmp_path / "a"))
        monkeypatch.setenv("OPSAGENT_COMPILE_CACHE", str(tmp_path / "b"))
        path = enable_compilation_cache()
        assert path == compile_cache_dir()
        assert os.path.dirname(path) == os.path.join(REPO, ".jax_cache")
        assert not (tmp_path / "a").exists() and not (tmp_path / "b").exists()

    def test_jax_switch_disables(self):
        """JAX's own switch turns the cache off; no repo spelling does."""
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            assert enable_compilation_cache() is None
        finally:
            jax.config.update("jax_enable_compilation_cache", True)


# -- /healthz init block -------------------------------------------------------
class TestHealthzInit:
    def test_init_block_reports_cold_start_provenance(
        self, tmp_path, cache_env, monkeypatch
    ):
        from opsagent_tpu.serving.api import ServingStack, build_engine_app

        eng, snapdir, man = _snap(tmp_path)
        eng2 = _teardown_and_restore(
            eng, snapdir, tmp_path, monkeypatch, warmup=False
        )
        stack = ServingStack(eng2)
        try:
            app = build_engine_app(stack)

            async def _get():
                client = TestClient(TestServer(app))
                await client.start_server()
                try:
                    resp = await client.get("/healthz")
                    return json.loads(await resp.text())
                finally:
                    await client.close()

            import asyncio

            body = asyncio.new_event_loop().run_until_complete(_get())
            init = body["init"]
            assert init["restore_source"] == os.path.abspath(snapdir)
            assert init["snapshot_fingerprint"] == man["fingerprint"]
            assert init["weights_load_s"] >= 0
            assert "warmup_s" in init
        finally:
            stack.close()
