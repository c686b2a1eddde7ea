"""Metrics-core tests: exposition golden (parseable Prometheus text,
histogram bucket cumulativity, label escaping), concurrency hammering,
the PerfStats bridge, and the bounded-series / timer-path fixes in
utils/perf.py."""

import re
import threading

import pytest

from opsagent_tpu import obs
from opsagent_tpu.obs.metrics import (
    Histogram,
    Registry,
    escape_label_value,
)
from opsagent_tpu.utils.perf import SERIES_WINDOW, PerfStats, get_perf_stats

# A sample line: name{labels} value — labels optional; value is a number
# ("+Inf" never appears as a VALUE, only inside a le label).
_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"          # metric name
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\[\\\"n])*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\[\\\"n])*\")*\})?"
    r" -?[0-9.e+-]+$"
)


def parse_exposition(text: str) -> dict[str, float]:
    """Validate every line of the exposition and return {sample: value}."""
    assert text.endswith("\n")
    samples: dict[str, float] = {}
    for line in text.splitlines():
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            continue
        assert _SAMPLE.match(line), f"malformed exposition line: {line!r}"
        key, val = line.rsplit(" ", 1)
        samples[key] = float(val)
    return samples


def test_exposition_golden():
    r = Registry()
    c = r.counter("req_total", "requests", labelnames=("path",))
    c.inc(path="/a")
    c.inc(2, path="/b")
    g = r.gauge("occupancy", "batch fill")
    g.set(0.5)
    h = r.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    text = r.render()
    assert "# HELP req_total requests\n# TYPE req_total counter" in text
    assert "# TYPE occupancy gauge" in text
    assert "# TYPE lat_seconds histogram" in text
    samples = parse_exposition(text)
    assert samples['req_total{path="/a"}'] == 1
    assert samples['req_total{path="/b"}'] == 2
    assert samples["occupancy"] == 0.5
    # Cumulativity: each bucket includes everything below it; +Inf == count.
    assert samples['lat_seconds_bucket{le="0.1"}'] == 1
    assert samples['lat_seconds_bucket{le="1"}'] == 3
    assert samples['lat_seconds_bucket{le="+Inf"}'] == 4
    assert samples["lat_seconds_count"] == 4
    assert samples["lat_seconds_sum"] == pytest.approx(6.05)


def test_label_escaping():
    assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
    r = Registry()
    c = r.counter("esc_total", labelnames=("k",))
    c.inc(k='quo"te\nnl\\bs')
    text = r.render()
    line = [l for l in text.splitlines() if l.startswith("esc_total")][0]
    assert "\n" not in line  # a raw newline would split the sample
    assert '\\"' in line and "\\n" in line and "\\\\" in line
    parse_exposition(text)


def test_histogram_boundary_lands_in_bucket():
    # Prometheus buckets are upper-INCLUSIVE: observe(le) counts in le.
    h = Histogram("h", "", (), buckets=(1.0, 2.0))
    h.observe(1.0)
    h.observe(2.0)
    lines = h.collect()
    assert 'h_bucket{le="1"} 1' in lines
    assert 'h_bucket{le="2"} 2' in lines


def test_registry_idempotent_and_type_conflict():
    r = Registry()
    a = r.counter("same_total", "first help")
    b = r.counter("same_total", "other help")
    assert a is b
    with pytest.raises(ValueError):
        r.gauge("same_total")
    with pytest.raises(ValueError):
        r.counter("0bad name")


def test_counters_and_histograms_under_contention():
    r = Registry()
    c = r.counter("hammer_total", labelnames=("t",))
    h = r.histogram("hammer_seconds", buckets=(0.5,))
    g = r.gauge("hammer_gauge")
    N, T = 500, 8

    def work(i: int) -> None:
        for j in range(N):
            c.inc(t=str(i % 3))
            h.observe(0.25 if j % 2 else 0.75)
            g.set(float(j))

    threads = [threading.Thread(target=work, args=(i,)) for i in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = sum(c.value(t=str(k)) for k in range(3))
    assert total == N * T
    assert h.count() == N * T
    samples = parse_exposition(r.render())
    assert samples['hammer_seconds_bucket{le="+Inf"}'] == N * T
    assert samples['hammer_seconds_bucket{le="0.5"}'] == N * T // 2


def test_perf_bridge_into_default_registry():
    get_perf_stats().record_metric("bridge.test", 12.5, "ms")
    get_perf_stats().set_gauge("bridge.gauge", 3.0)
    text = obs.metrics_text()
    samples = parse_exposition(text)
    assert samples[
        'opsagent_perf{series="bridge.test",stat="count",unit="ms"}'
    ] == 1
    assert samples[
        'opsagent_perf{series="bridge.test",stat="avg",unit="ms"}'
    ] == 12.5
    assert samples[
        'opsagent_perf{series="bridge.gauge",stat="gauge",unit=""}'
    ] == 3.0


def test_snapshot_is_compact_and_json_safe():
    import json

    r = Registry()
    r.counter("snap_total", labelnames=("k",)).inc(3, k="x")
    r.histogram("snap_seconds", buckets=(1.0,)).observe(0.5)
    snap = r.snapshot()
    assert snap['snap_total{k="x"}'] == 3
    assert snap["snap_seconds_count"] == 1
    assert snap["snap_seconds_sum"] == 0.5
    json.dumps(snap)  # must be serializable straight into BENCH_*.json


# -- utils/perf.py satellites -------------------------------------------------
def test_perf_series_memory_is_bounded():
    ps = PerfStats()
    n = SERIES_WINDOW + 500
    for i in range(n):
        ps.record_metric("busy", float(i), "ms")
    s = ps.get_stats()["busy"]
    # count/avg/min/max exact over ALL observations; window bounds memory.
    assert s["count"] == n
    assert s["min"] == 0.0
    assert s["max"] == float(n - 1)
    assert s["avg"] == pytest.approx((n - 1) / 2)
    assert len(ps._series["busy"].values) == SERIES_WINDOW
    # percentiles come from the recent window
    assert s["p50"] >= 500.0


def test_perf_reset_keeps_inflight_timers():
    ps = PerfStats()
    ps.start_timer("op")
    ps.reset()  # lands mid-request
    ms = ps.stop_timer("op")
    assert ms > 0.0
    assert ps.get_stats()["op"]["count"] == 1


def test_perf_timer_paths_unified():
    ps = PerfStats()
    ps.start_timer("op")
    ps.stop_timer("op")
    with ps.timer("op"):
        pass
    s = ps.get_stats()["op"]
    assert s["count"] == 2
    assert s["unit"] == "ms"
    # disabled registry records nothing on ANY path
    ps.enabled = False
    ps.start_timer("op")
    assert ps.stop_timer("op") == 0.0
    assert ps.get_stats()["op"]["count"] == 2


@pytest.mark.parametrize("depth", [1, 2], ids=["sync", "async"])
@pytest.mark.parametrize("rows,budget,chunks,computed,step_rows,scattered", [
    (4, 128, 1, 64, "rows", 64),    # 4 x 16 slots are half the step's 128
    (16, 128, 1, 64, "packed:128", 128),    # 17 tokens: the narrow width
    (16, 128, 4, 128, "packed:128", 128),   # 65 tokens: the whole packed width
    (8, 128, 1, 64, "packed:128", 128),     # 8 x 16 slots ARE the step's 128
    (16, 256, 1, 128, "packed:256", 256),   # cell 4's [16, 16] at 256
], ids=["rows", "packed_narrow", "packed_wide", "slots_equal_step_tokens",
        "16_rows_of_16_at_256"])
def test_step_tokens_counts_what_a_mixed_dispatch_carried_and_computed(
        depth, rows, budget, chunks, computed, step_rows, scattered):
    """opsagent_step_tokens_total, at dispatch on both mixed paths:
    kind=real the tokens carried (one decode lane and chunks of 16),
    kind=computed the rows the dense segments ran over: rows x bucket where
    that is no more than half the step's tokens (``max_step_tokens``);
    where the program packs, half the step's tokens in a tick that carries
    no more and the packed width in any other.
    opsagent_mixed_dispatch_width_total counts the dispatch under that
    width, and ``impl.step_rows`` says whether the widest program packs.
    opsagent_kv_write_rows_total: kind=scattered the rows the page write's
    scatter walks a layer (the packed width whatever the tick carries,
    where the program writes by token: ``impl.kv_write`` "tokens"; else
    its slots), kind=real the tokens that land."""
    import jax.numpy as jnp

    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    eng = Engine(EngineConfig(
        model="tiny-test", dtype=jnp.float32, tp=1, page_size=4,
        num_pages=64 * rows, max_pages_per_seq=24, max_batch_size=rows,
        prefill_buckets=(8, 16), decode_block=4, mixed_buckets=(16,),
        max_step_tokens=budget, async_depth=depth,
    ))
    assert eng.impl_info()["step_rows"] == step_rows
    assert eng.impl_info()["kv_write"] == (
        "rows" if step_rows == "rows" else "tokens")
    lane = eng.add_request([257, 9, 8, 7], SamplingParams(max_tokens=8))
    admits = [
        eng.begin_request(
            [257] + list(range(1 + i, 40 + i)), SamplingParams(max_tokens=4))
        for i in range(chunks)]

    def read(name, **labels):
        key = ",".join(f'{k}="{v}"' for k, v in labels.items())
        return obs.metrics_snapshot().get(f"{name}{{{key}}}", 0.0)

    before = (read("opsagent_step_tokens_total", kind="real"),
              read("opsagent_step_tokens_total", kind="computed"),
              read("opsagent_mixed_dispatch_width_total", width=computed),
              read("opsagent_kv_write_rows_total", kind="real"),
              read("opsagent_kv_write_rows_total", kind="scattered"))
    step = eng.step_mixed if depth == 1 else eng.step_mixed_async
    step([lane], {a: 16 for a in admits})
    eng.drain()
    assert read("opsagent_step_tokens_total", kind="real") - before[0] == (
        1 + 16 * chunks)
    assert read(
        "opsagent_step_tokens_total", kind="computed") - before[1] == computed
    assert read(
        "opsagent_mixed_dispatch_width_total", width=computed) - before[2] == 1
    assert read("opsagent_kv_write_rows_total", kind="real") - before[3] == (
        1 + 16 * chunks)
    assert read(
        "opsagent_kv_write_rows_total", kind="scattered") - before[4] == (
            scattered)
