"""Device-level profiling (utils/profiling.py): jax.profiler traces, host
annotations and kernel scopes — SURVEY §5's TPU additions over the
reference's host-only timer registry (reference pkg/utils/perf.go:168-210).
Tick phases, the step clock and the scopes of the step programs are in
tests/test_tick_tracing.py."""

import os

import jax
import jax.numpy as jnp

from opsagent_tpu.utils import profiling


def test_trace_noop_without_dir(monkeypatch):
    monkeypatch.delenv("OPSAGENT_PROFILE_DIR", raising=False)
    with profiling.trace():  # must not start a real trace
        jnp.ones((4,)).block_until_ready()


def test_trace_writes_capture(tmp_path, monkeypatch):
    logdir = tmp_path / "prof"
    with profiling.trace(str(logdir)):
        jax.jit(lambda x: x * 2)(jnp.ones((8, 8))).block_until_ready()
    files = [
        os.path.join(r, f) for r, _, fs in os.walk(logdir) for f in fs
    ]
    assert files, "jax.profiler trace produced no capture files"


def test_annotate_is_free_outside_trace():
    with profiling.annotate("unit-test-region"):
        pass
