"""The readers of the host's side of a tick (PR 38), on hand-made scrapes,
and the idle gap that is labelled by the part of a phase the host was in.

No engine and no profile: ``reduce_planes`` takes plain tuples, a reader
takes two parsed scrapes.
"""

import pytest

from benchmarks import client, trace_reduce
from benchmarks.loading import load_module

MS = 1e6    # nanoseconds


def test_an_idle_gap_under_a_part_is_labelled_by_the_part():
    """The thread is in ``engine.admit``, then in ``engine.admit.match`` and
    not in ``engine.admit`` (a part suspends its phase's annotation as a
    nested phase does), then in ``engine.admit`` again; the device idles
    while the match runs."""
    host = ("/host:CPU", [("scheduler", [
        ("engine.admit", 0.0, 10 * MS, {}),
        ("engine.admit.match", 10 * MS, 20 * MS, {}),
        ("engine.admit", 30 * MS, 5 * MS, {}),
        ("engine.dispatch", 35 * MS, 1 * MS, {}),
        ("engine.dispatch.call", 36 * MS, 4 * MS, {}),
        ("engine.mixed_step_async", 36 * MS, 3.9 * MS, {}),
    ])])
    device = ("/device:TPU:0", [("XLA Ops", [
        ("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p)", 0.0, 12 * MS, {}),
        ("%fusion.2 = f32[4]{0} fusion(f32[4]{0} %p)", 28 * MS, 12 * MS, {}),
    ])])
    got = trace_reduce.reduce_planes([host, device], chips=1)
    assert got["idle_gaps"][0][0] == "engine.admit.match"
    assert got["idle_gaps"][0][1] == pytest.approx(0.016)
    assert got["annotations"]["engine.admit"] == 2
    assert got["annotations"]["engine.admit.match"] == 1
    # the step annotations keep their names and counts inside `call`
    assert got["steps"] == 1
    assert got["annotations"]["engine.mixed_step_async"] == 1


BEFORE = """
opsagent_ticks_total 100
opsagent_tick_phase_seconds_total{phase="admit"} 1
opsagent_tick_phase_seconds_total{phase="plan"} 2
opsagent_tick_phase_seconds_total{phase="dispatch"} 3
opsagent_tick_phase_seconds_total{phase="commit"} 1
opsagent_tick_phase_seconds_total{phase="reap"} 0.5
opsagent_tick_phase_seconds_total{phase="wait"} 4
opsagent_tick_phase_seconds_total{phase="idle"} 9
opsagent_tick_part_seconds_total{phase="admit",part="match"} 0.5
opsagent_tick_part_seconds_total{phase="plan",part="arrays"} 1
opsagent_tick_part_seconds_total{phase="plan",part="account"} 0.25
opsagent_tick_part_seconds_total{phase="dispatch",part="place"} 1
opsagent_tick_part_seconds_total{phase="dispatch",part="call"} 2
opsagent_tick_part_seconds_total{phase="commit",part="account"} 0.25
opsagent_tick_part_seconds_total{phase="wait",part="alone"} 1
opsagent_tick_part_seconds_total{phase="wait",part="pipelined"} 3
opsagent_tick_host_work_seconds_bucket{le="0.005"} 10
opsagent_tick_host_work_seconds_bucket{le="0.01"} 50
opsagent_tick_host_work_seconds_bucket{le="0.015"} 100
opsagent_tick_host_work_seconds_bucket{le="+Inf"} 100
opsagent_tick_host_work_seconds_count 100
opsagent_tick_host_work_seconds_sum 0.9
opsagent_step_late_pulls_total{program="mixed"} 10
opsagent_step_device_seconds_count{program="mixed",bucket="16",width="128"} 40
opsagent_step_device_seconds_sum{program="mixed",bucket="16",width="128"} 0.8
opsagent_step_device_seconds_count{program="mixed",bucket="16",width="256"} 40
opsagent_step_device_seconds_sum{program="mixed",bucket="16",width="256"} 1.6
opsagent_step_device_seconds_count{program="decode_block",bucket="8",width=""} 10
opsagent_step_device_seconds_sum{program="decode_block",bucket="8",width=""} 1
opsagent_mixed_dispatch_width_total{width="128"} 50
opsagent_mixed_dispatch_width_total{width="256"} 50
opsagent_admission_seconds_count{outcome="admitted"} 10
opsagent_admission_seconds_sum{outcome="admitted"} 0.1
opsagent_request_decode_ticks_total 500
opsagent_request_decode_tokens_total 1000
"""

# 200 ticks later: admit +2 s (match +1.5), plan +3 (arrays +1, account
# +0.5), dispatch +4 (place +1, call +2.5), commit +1 (account +0.25), reap
# +0.5 (nothing named), wait +6 (alone +1.5, pipelined +4.5); 200 more
# observations of a tick's host work, 20 up to 5 ms, 100 up to 10, 190 up to
# 15 and 10 beyond every bound; 30 late pulls beside 100 + 50 mixed samples
# (20 and 40 ms) and 20 blocks; 50 narrow dispatches and 150 wide ones; 20
# admissions of 10 ms and 5 attempts of 30 ms that ended out of pages; 900
# ticks for 1000 tokens.
AFTER = """
opsagent_ticks_total 300
opsagent_tick_phase_seconds_total{phase="admit"} 3
opsagent_tick_phase_seconds_total{phase="plan"} 5
opsagent_tick_phase_seconds_total{phase="dispatch"} 7
opsagent_tick_phase_seconds_total{phase="commit"} 2
opsagent_tick_phase_seconds_total{phase="reap"} 1
opsagent_tick_phase_seconds_total{phase="wait"} 10
opsagent_tick_phase_seconds_total{phase="idle"} 9
opsagent_tick_part_seconds_total{phase="admit",part="match"} 2
opsagent_tick_part_seconds_total{phase="plan",part="arrays"} 2
opsagent_tick_part_seconds_total{phase="plan",part="account"} 0.75
opsagent_tick_part_seconds_total{phase="dispatch",part="place"} 2
opsagent_tick_part_seconds_total{phase="dispatch",part="call"} 4.5
opsagent_tick_part_seconds_total{phase="commit",part="account"} 0.5
opsagent_tick_part_seconds_total{phase="wait",part="alone"} 2.5
opsagent_tick_part_seconds_total{phase="wait",part="pipelined"} 7.5
opsagent_tick_host_work_seconds_bucket{le="0.005"} 30
opsagent_tick_host_work_seconds_bucket{le="0.01"} 150
opsagent_tick_host_work_seconds_bucket{le="0.015"} 290
opsagent_tick_host_work_seconds_bucket{le="+Inf"} 300
opsagent_tick_host_work_seconds_count 300
opsagent_tick_host_work_seconds_sum 3.1
opsagent_step_late_pulls_total{program="mixed"} 40
opsagent_step_device_seconds_count{program="mixed",bucket="16",width="128"} 140
opsagent_step_device_seconds_sum{program="mixed",bucket="16",width="128"} 2.8
opsagent_step_device_seconds_count{program="mixed",bucket="16",width="256"} 90
opsagent_step_device_seconds_sum{program="mixed",bucket="16",width="256"} 3.6
opsagent_step_device_seconds_count{program="decode_block",bucket="8",width=""} 30
opsagent_step_device_seconds_sum{program="decode_block",bucket="8",width=""} 3
opsagent_mixed_dispatch_width_total{width="128"} 100
opsagent_mixed_dispatch_width_total{width="256"} 200
opsagent_admission_seconds_count{outcome="admitted"} 30
opsagent_admission_seconds_sum{outcome="admitted"} 0.3
opsagent_admission_seconds_count{outcome="out_of_pages"} 5
opsagent_admission_seconds_sum{outcome="out_of_pages"} 0.15
opsagent_request_decode_ticks_total 1400
opsagent_request_decode_tokens_total 2000
"""

READERS = {
    "engine.admit_ms_mean": 10.0,
    "engine.plan_ms_mean": 15.0,
    "engine.dispatch_ms_mean": 20.0,
    "engine.commit_ms_mean": 5.0,
    "engine.admit_match_ms": 7.5,
    "engine.plan_arrays_ms": 5.0,
    "engine.dispatch_place_ms": 5.0,
    "engine.dispatch_call_ms": 12.5,
    # plan's +0.5 and commit's +0.25 over 200 ticks
    "engine.account_ms": 3.75,
    # the work phases took 10.5 s; named: 1.5 + 1.5 + 3.5 + 0.25
    "engine.parts_named_share": 100.0 * 6.75 / 10.5,
    # rank 190 of 200 is the last of the 140 in (10, 15] ms
    "engine.host_work_p95_ms": 15.0,
    "engine.wait_alone_share": 25.0,
    "engine.late_pull_share": 100.0 * 30 / (30 + 170),
    # 20 x 10 ms and 5 x 30 ms
    "sched.admission_ms_mean": 14.0,
    "sched.ticks_per_token": 0.9,
    # a quarter of the dispatches ran 128 rows at 20 ms, the rest 256 at 40
    "step.mixed_ms_by_width": 35.0,
}


def ctx(before: str, after: str) -> dict:
    return {"before": client.parse_metrics(before),
            "after": client.parse_metrics(after),
            "counts": {"window_s": 10.0}, "trace": None}


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_gives_nothing_on_an_empty_scrape_and_the_right_number(name):
    reader = load_module("layer_metrics", name)
    assert reader.read(ctx("", "")) is None
    assert reader.read(ctx(BEFORE, AFTER)) == pytest.approx(READERS[name])


def without(text: str, *families: str) -> str:
    return "\n".join(ln for ln in text.splitlines()
                     if not ln.startswith(families))


NEW_FAMILIES = (
    "opsagent_tick_part_seconds_total", "opsagent_tick_host_work_seconds",
    "opsagent_admission_seconds", "opsagent_request_decode_",
)


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_parents_scrape_reads_only_what_the_parent_had(name):
    """A program from before the parts: the new families are absent and the
    step clock has no ``width`` label. The readers of the existing families
    read what they read; every other one gives nothing and does not raise."""
    def parents(text: str) -> str:
        return without(text, *NEW_FAMILIES).replace(',width="128"', "") \
            .replace(',width="256"', "").replace(',width=""', "")

    value = load_module("layer_metrics", name).read(
        ctx(parents(BEFORE), parents(AFTER)))
    existing = {"engine.admit_ms_mean", "engine.plan_ms_mean",
                "engine.dispatch_ms_mean", "engine.commit_ms_mean",
                "engine.late_pull_share"}
    if name in existing:
        assert value == pytest.approx(READERS[name])
    else:
        assert value is None


def test_the_existing_step_clock_readers_sum_over_the_width_label():
    got = load_module("layer_metrics", "step.mixed_ms_mean").read(
        ctx(BEFORE, AFTER))
    # 100 samples of 20 ms and 50 of 40: the mean of what was sampled
    assert got == pytest.approx(4.0 / 150 * 1e3)


def test_a_width_that_ran_and_left_no_sample_gives_no_number(capsys):
    reader = load_module("layer_metrics", "step.mixed_ms_by_width")
    wide = 'opsagent_step_device_seconds_'
    no_wide = "\n".join(
        ln for ln in AFTER.splitlines()
        if not (ln.startswith(wide) and 'width="256"' in ln))
    assert reader.read(ctx(without(BEFORE, wide), no_wide)) is None
    assert "no step-clock sample at width ['256']" in capsys.readouterr().out
    # a width that ran in under one dispatch in a hundred is left out
    rare = AFTER + '\nopsagent_mixed_dispatch_width_total{width="512"} 1\n'
    assert reader.read(ctx(BEFORE, rare)) == pytest.approx(35.0)


def test_every_new_entry_has_a_case_here():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    first = names.index("engine.admit_ms_mean")
    assert set(names[first:first + len(READERS)]) == set(READERS)
