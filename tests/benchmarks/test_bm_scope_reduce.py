"""``benchmarks/scope_reduce.py`` and the readers built on it: the wire
reader on hand-made planes and on the two recorded chip traces, the
attribution to the innermost scope, a ``while`` less its body, the mean
duration of each program, and every new reader on what it reads.
"""

import gzip
import json
import os
import struct

import pytest

from benchmarks import scope_reduce, trace_reduce
from benchmarks.loading import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
OLD = os.path.join(HERE, "tiny_tpu.xplane.pb.gz")           # PR 23, no scopes
SCOPED = os.path.join(HERE, "tiny_tpu_scoped.xplane.pb.gz")  # PR 24


# -- a protobuf writer for hand-made planes -------------------------------------
def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number: int, value) -> bytes:
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    if isinstance(value, float):
        return varint(number << 3 | 1) + struct.pack("<d", value)
    data = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(data)) + data


def xstat(sid: int, value) -> bytes:
    """A ("ref", id) tuple as ``ref_value``, a str as ``str_value``, a float
    as ``double_value``, an int as ``uint64_value``."""
    number = {tuple: 7, str: 5, float: 2, int: 3}[type(value)]
    return field(1, sid) + field(
        number, value[1] if isinstance(value, tuple) else value)


def xplane(name: str, lines: list, stat_names: dict[int, str]) -> bytes:
    """lines: [(line name, t0_ns, [(event name, offset_ps, duration_ps,
    {stat id: value})])]. Each distinct event gets a metadata entry, and
    the stats go there, where the profiler puts those of an operation."""
    meta: dict[str, tuple] = {}
    out = field(2, name)
    for line_name, t0, events in lines:
        body = field(2, line_name) + field(3, t0)
        for ev_name, off, dur, stats in events:
            mid = meta.setdefault(ev_name, (len(meta) + 1, stats))[0]
            body += field(4, field(1, mid) + field(2, off) + field(3, dur))
        out += field(3, body)
    for ev_name, (mid, stats) in meta.items():
        entry = field(1, mid) + field(2, ev_name) + b"".join(
            field(5, xstat(sid, value)) for sid, value in stats.items())
        out += field(4, field(1, mid) + field(2, entry))
    for sid, sname in stat_names.items():
        out += field(5, field(1, sid) + field(2, field(1, sid) + field(2, sname)))
    return out


def write_xspace(path, planes: list[bytes]) -> str:
    data = b"".join(field(1, p) for p in planes)
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "wb") as f:
        f.write(data)
    return str(path)


STATS = {1: "tf_op", 2: "flops", 3: "hlo_category", 4: "bytes_accessed",
         9: "jit(step)/while/body/ffn/dot_general"}
US = 1_000_000   # picoseconds


def scoped_planes() -> list[bytes]:
    """One chip: a ``while`` of 100 us whose body holds a gather nested in
    ``attn_core`` (30 us), an ``ffn`` matmul named through a ref stat (40
    us) and a copy with no ``tf_op`` (10 us): 20 us are the loop's own."""
    ops = [
        ("%while.1 = while()", 0, 100 * US,
         {1: "jit(step)/while", 3: "while"}),
        ("%gather.2 = gather()", 5 * US, 30 * US,
         {1: "jit(step)/while/body/attn_core/attn_core/kv_gather/gather",
          2: 0, 4: 4096}),
        ("%fusion.3 = fusion()", 40 * US, 40 * US,
         {1: ("ref", 9), 2: 123.0}),
        ("%copy.4 = copy()", 85 * US, 10 * US, {3: "data formatting"}),
        ("%fusion.5 = fusion()", 120 * US, 50 * US,
         {1: "jit(step)/lm_head/dot_general"}),
    ]
    modules = [("jit_step(123)", 0, 60 * US, {}),          # cut by the start
               ("jit_step(123)", 70 * US, 170 * US, {}),
               ("jit__threefry_split(9)", 250 * US, 2 * US, {}),
               ("jit_step(123)", 260 * US, 190 * US, {}),
               ("jit_step(123)", 460 * US, 35 * US, {})]        # and the end
    device = xplane("/device:TPU:0", [
        ("XLA Modules", 1000, modules), ("XLA Ops", 1000, ops)], STATS)
    host = xplane("/host:CPU", [("python3", 1000, [
        ("engine.mixed_step_async", 0, 20 * US, {}),
        ("engine.wait", 20 * US, 150 * US, {})])], {})
    return [device, host]


# -- the wire reader and the reduction ------------------------------------------
def test_the_wire_reader_finds_names_times_and_metadata_stats(tmp_path):
    path = write_xspace(tmp_path / "t.xplane.pb", scoped_planes())
    planes = dict(scope_reduce.read_planes(path))
    ops = dict(planes["/device:TPU:0"])["XLA Ops"]
    name, start_ns, dur_ns, stats = ops[1]
    assert name == "%gather.2 = gather()"
    assert (start_ns, dur_ns) == (1000 + 5_000.0, 30_000.0)
    assert stats["tf_op"].endswith("kv_gather/gather")
    assert stats["bytes_accessed"] == 4096
    # a ref_value stat reads as the string it refers to, a double as a float
    assert ops[2][3] == {"tf_op": STATS[9], "flops": 123.0}
    assert dict(planes["/host:CPU"])["python3"][1][0] == "engine.wait"


@pytest.mark.parametrize("gz", [False, True])
def test_own_time_goes_to_the_innermost_scope_and_a_while_less_its_body(tmp_path, gz):
    path = write_xspace(
        tmp_path / ("t.xplane.pb" + (".gz" if gz else "")), scoped_planes())
    got = scope_reduce.reduce_file(path)
    us = {k: round(v * 1e6, 6) for k, v in got["scope_s"].items()}
    assert us["kv_gather"] == 30 and us["attn_core"] == 0   # innermost wins
    assert us["ffn"] == 40 and us["lm_head"] == 50
    assert us["unscoped"] == 30                 # the loop's own 20 + the copy
    assert sum(us.values()) == 150              # not 100 + 80 + 50
    assert got["unscoped_ops"][0] == ["while.1 while() ", pytest.approx(20e-6)]
    assert got["ops"] == 5 and got["ops_with_tf_op"] == 4
    assert got["module_s"] == {
        "jit_step": pytest.approx(180e-6),
        "jit__threefry_split": pytest.approx(2e-6)}
    assert got["module_n"] == {"jit_step": 2, "jit__threefry_split": 1}
    # the same operation time as the kept reduction counts
    old = trace_reduce.reduce_planes(scope_reduce.read_planes(path))
    assert sum(got["scope_s"].values()) == pytest.approx(old["op_sum_s"])


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(_mixed_carry)/while/body/closed_call/attn_core/kv_gather/gather", "kv_gather"),
    ("jit(_mixed_carry)/while/body/closed_call/ffn/dot_general", "ffn"),
    ("jit(_mixed_carry)/sample/jit(_gumbel)/jit(_uniform)/slice", "sample"),
    ("jit(_mixed_carry)/while/body/closed_call/dot_general", "unscoped"),
    # operations XLA merged: the first path counts
    ("jit(f)/while/body/attn_core/attn_core/kv_gather/reshape;kv_write/kv_write/reshape:", "kv_gather"),
    ("jit(ffn_like)/embedding/gather", "unscoped"),     # whole parts only
    ("", "unscoped"),
    (None, "unscoped"),
])
def test_scope_of_a_path(tf_op, scope):
    assert scope_reduce.scope_of(tf_op) == scope


def test_the_first_recorded_trace_has_the_stats_and_no_scope():
    """PR 23's chip recording, taken before the program had scopes: the
    metadata stats are there, every model scope reads 0, the own times add
    up to ``trace_reduce``'s and the mixed program has a duration."""
    got = scope_reduce.reduce_file(OLD)
    assert got["devices"] == 1
    assert got["ops_with_tf_op"] > 0.6 * got["ops"] > 10_000
    model = [s for s in scope_reduce.SCOPES if s != "sample"]
    assert all(got["scope_s"][s] == 0.0 for s in model)
    total = sum(got["scope_s"].values())
    # (JAX's own threefry code names a "sample" on its paths: 1%)
    assert got["scope_s"]["unscoped"] > 0.98 * total
    old = trace_reduce.reduce_file(OLD, chips=1)
    # (the profiler's own reader rounds each duration to a nanosecond)
    assert total == pytest.approx(old["op_sum_s"], rel=1e-3)
    # 149 executions, less the one that the capture's end cut short (the
    # line's first event is another program's)
    assert got["module_n"]["jit__mixed_carry"] == 148
    assert 50e-6 < got["module_s"]["jit__mixed_carry"] < 1e-3
    planes = dict(scope_reduce.read_planes(OLD))
    stats = dict(planes["/device:TPU:0"])["XLA Ops"][5][3]
    assert {"hlo_category", "flops", "bytes_accessed", "program_id"} <= set(stats)


def test_the_second_recorded_trace_carries_the_programs_scopes():
    """PR 24's chip recording at tiny size (a 0.5 s capture of the
    long-generate rehearsal on a TPU v5 lite): every scope of the
    vocabulary has device time, little is left unscoped, both step programs
    have whole executions, and the tick phases are on the host plane."""
    got = scope_reduce.reduce_file(SCOPED)
    assert got["devices"] == 1 and got["ops"] > 10_000
    assert all(got["scope_s"][s] > 0 for s in scope_reduce.SCOPES)
    total = sum(got["scope_s"].values())
    assert got["scope_s"]["unscoped"] < 0.15 * total
    # at tiny size the page write and the gather are most of a step
    assert got["scope_s"]["kv_write"] + got["scope_s"]["kv_gather"] > 0.5 * total
    old = trace_reduce.reduce_file(SCOPED, chips=1)
    assert total == pytest.approx(old["op_sum_s"], rel=1e-3)
    assert got["module_n"]["jit__mixed_carry"] > 40
    assert got["module_n"]["jit__decode_pipeline"] > 10
    # a fused block of 4 passes takes longer than a mixed step
    assert got["module_s"]["jit__decode_pipeline"] > got["module_s"]["jit__mixed_carry"]
    phases = {"engine." + p for p in
              ("admit", "plan", "dispatch", "wait", "commit", "reap", "idle")}
    assert phases <= set(old["annotations"])
    assert old["annotations"]["engine.dispatch"] == old["steps"]
    assert {name for name, _ in old["idle_gaps"]} & phases


# -- the readers ------------------------------------------------------------------
NEW_READERS = [
    "engine.host_work_ms_mean", "engine.device_wait_share",
    "step.mixed_ms_mean", "step.block_pass_ms_mean", "api.emit_lag_mean_ms",
    "kernels.attn_core_ms", "kernels.kv_gather_ms", "kernels.kv_write_ms",
    "kernels.proj_ms", "kernels.ffn_ms", "kernels.head_ms",
    "kernels.unscoped_ms",
]


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_returns_none_where_its_source_is_absent(name):
    """The parent program has no such counter, a CPU rehearsal no device
    plane: the reader gives nothing and does not raise."""
    ctx = {"before": {}, "after": {}, "config": {"engine": {"decode_block": 8}},
           "trace": {"devices": 0, "annotations": {}, "window_s": 0.0}}
    assert load_module("layer_metrics", name).read(ctx) is None
    assert load_module("layer_metrics", name).read(dict(ctx, trace=None)) is None


def scrape(phases: dict, ticks: float, steps: dict, lag: tuple) -> dict:
    out = {
        "opsagent_tick_phase_seconds_total": [
            ({"phase": p}, v) for p, v in phases.items()],
        "opsagent_ticks_total": [({}, ticks)],
        "opsagent_stream_emit_lag_seconds_sum": [({}, lag[0])],
        "opsagent_stream_emit_lag_seconds_count": [({}, lag[1])],
    }
    for (program, bucket), (total, n) in steps.items():
        labels = {"program": program, "bucket": bucket}
        out.setdefault("opsagent_step_device_seconds_sum", []).append((labels, total))
        out.setdefault("opsagent_step_device_seconds_count", []).append((labels, n))
    return out


def test_counter_readers_take_the_windows_delta():
    before = scrape(
        {"admit": 1.0, "plan": 1.0, "dispatch": 1.0, "wait": 10.0,
         "commit": 1.0, "reap": 1.0, "idle": 50.0}, 100,
        {("mixed", "16"): (5.0, 10), ("mixed", "32"): (1.0, 1),
         ("decode_block", "8"): (2.0, 10)}, (0.5, 100))
    after = scrape(
        {"admit": 1.2, "plan": 1.5, "dispatch": 1.1, "wait": 19.0,
         "commit": 1.15, "reap": 1.05, "idle": 52.0}, 150,
        {("mixed", "16"): (9.0, 20), ("mixed", "32"): (7.0, 11),
         ("decode_block", "8"): (3.6, 20)}, (0.8, 400))
    ctx = {"before": before, "after": after, "counts": {"window_s": 12.0},
           "config": {"engine": {"decode_block": 8}}, "trace": None}

    def read(name):
        return load_module("layer_metrics", name).read(ctx)

    assert read("engine.host_work_ms_mean") == pytest.approx(1000.0 / 50)
    assert read("engine.device_wait_share") == pytest.approx(90.0)
    assert read("step.mixed_ms_mean") == pytest.approx(10_000.0 / 20)
    assert read("step.block_pass_ms_mean") == pytest.approx(1600.0 / 80)
    assert read("api.emit_lag_mean_ms") == pytest.approx(1.0)


def test_kernel_readers_charge_a_pass_its_scopes(tmp_path, monkeypatch):
    """The seven readers over the newest capture under ``.bench_out``: a
    mixed step and a decode block of 8 passes make 9 passes."""
    trace_dir = tmp_path / ".bench_out" / "cell" / "trace" / "plugins" / "profile" / "x"
    trace_dir.mkdir(parents=True)
    write_xspace(trace_dir / "host.xplane.pb", scoped_planes())
    monkeypatch.setattr(scope_reduce, "ROOT", str(tmp_path))
    ctx = {"trace": {"devices": 1, "annotations": {
               "engine.mixed_step_async": 1, "engine.decode_block": 1,
               "engine.wait": 2}},
           "config": {"engine": {"decode_block": 8}}}
    ms = {n: load_module("layer_metrics", n).read(ctx) for n in NEW_READERS[5:]}
    assert ms["kernels.kv_gather_ms"] == pytest.approx(0.030 / 9)
    assert ms["kernels.ffn_ms"] == pytest.approx(0.040 / 9)
    assert ms["kernels.head_ms"] == pytest.approx(0.050 / 9)
    assert ms["kernels.unscoped_ms"] == pytest.approx(0.030 / 9)
    assert ms["kernels.attn_core_ms"] == ms["kernels.proj_ms"] == 0.0
    assert sum(ms.values()) == pytest.approx(0.150 / 9)


# -- the names a family adds -------------------------------------------------------
def test_with_no_added_name_every_scope_reads_what_it_read():
    """The two cells' family adds none: the vocabulary is the nine, and a
    family's empty list changes no scope's time."""
    base = scope_reduce.reduce_file(SCOPED)
    assert set(base["scope_s"]) == {*scope_reduce.SCOPES, "unscoped"}
    assert len(scope_reduce.SCOPES) == 9
    same = scope_reduce.reduce_planes(scope_reduce.read_planes(SCOPED), extra=())
    assert same["scope_s"] == base["scope_s"]
    # a name of the nine given again is no tenth scope
    again = scope_reduce.reduce_planes(
        scope_reduce.read_planes(SCOPED), extra=("ffn",))
    assert again["scope_s"] == base["scope_s"]


@pytest.mark.parametrize("name,enclosers", [
    # the draw inside the sampler: JAX's own jit name on the path
    ("jit(_gumbel)", {"sample"}),
    # the scores' contraction inside the attention core
    ("bkgd,blkd->bkgl", {"attn_core"}),
    # one that lies inside several of the nine, and outside them all
    ("dot_general:", {"attn_qkv", "attn_core", "attn_out", "ffn", "lm_head",
                      "unscoped"}),
])
def test_an_added_name_takes_exactly_what_its_enclosers_lose(name, enclosers):
    """On the chip recording: a name that lies on operation paths inside
    the nine is charged the own time of the operations under it, the scopes
    that enclose it lose exactly that, the others keep theirs, and the total
    does not change."""
    planes = scope_reduce.read_planes(SCOPED)
    base = scope_reduce.reduce_planes(planes)["scope_s"]
    got = scope_reduce.reduce_planes(planes, extra=(name,))["scope_s"]
    assert set(got) == {*base, name}
    assert got[name] > 0
    lost = {s: base[s] - got[s] for s in base}
    assert {s for s, x in lost.items() if x > 1e-12} <= enclosers
    assert all(x >= 0 for x in lost.values())
    assert sum(lost.values()) == pytest.approx(got[name], rel=1e-9)
    assert sum(got.values()) == pytest.approx(sum(base.values()), rel=1e-12)
    assert scope_reduce.scope_of(
        f"jit(f)/while/body/ffn/{name}/mul", (*scope_reduce.SCOPES, name)) == name
    assert scope_reduce.scope_of(f"jit(f)/while/body/ffn/{name}/mul") == "ffn"


def test_the_readers_answer_for_a_familys_name(tmp_path, monkeypatch):
    """``scope_ms_per_pass`` over a capture, with the cell's family found by
    the name in its configuration: the nine as before, and a name the
    family adds (here a family made for the test, whose program names the
    gather inside the attention core)."""
    from benchmarks import loading

    trace_dir = tmp_path / ".bench_out" / "cell" / "trace" / "plugins" / "profile" / "x"
    trace_dir.mkdir(parents=True)
    write_xspace(trace_dir / "host.xplane.pb", scoped_planes())
    monkeypatch.setattr(scope_reduce, "ROOT", str(tmp_path))
    families = tmp_path / "families"
    families.mkdir()
    with open(os.path.join(ROOT, "benchmarks", "families", "qwen2.py")) as f:
        text = f.read()
    assert "SCOPES = ()" in text
    (families / "naming.py").write_text(
        text.replace("SCOPES = ()", 'SCOPES = ("gather",)'))
    (families / "qwen2.py").write_text(text)
    monkeypatch.setattr(loading, "HERE", str(tmp_path))
    ctx = {"trace": {"devices": 1, "annotations": {
               "engine.mixed_step_async": 1, "engine.decode_block": 1}},
           "config": {"engine": {"decode_block": 8}, "family": "qwen2"}}
    assert scope_reduce.scope_ms_per_pass(ctx, "kv_gather") == pytest.approx(0.030 / 9)
    with pytest.raises(KeyError):
        scope_reduce.scope_ms_per_pass(ctx, "gather")
    ctx["config"]["family"] = "naming"
    assert scope_reduce.scope_ms_per_pass(ctx, "gather") == pytest.approx(0.030 / 9)
    assert scope_reduce.scope_ms_per_pass(ctx, "kv_gather") == 0.0
    assert scope_reduce.scope_ms_per_pass(ctx, "ffn", "lm_head", "unscoped") == (
        pytest.approx(0.120 / 9))
