"""The yardstick's arithmetic: bytes, the trace reduction, tokens, the
constraint walker and the generator's determinism. No device needed."""

import json
import os
import random

import pytest

from benchmarks import bytes_model, check, tokens, trace_reduce
from benchmarks.generators import closed_sessions
from benchmarks.loading import load_data, load_module

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))


def config(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


def traffic(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "traffic", name + ".json")) as f:
        return json.load(f)


# -- bytes, hand-worked -------------------------------------------------------
def test_bytes_of_the_7b_by_hand():
    c = config("qwen25-7b-int8")
    # a layer: q 3584x3584, k and v 3584x512, o 3584x3584, gate/up/down
    # 3584x18944: 233,046,016 int8 parameters
    matrices = 2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944
    assert matrices == 233_046_016
    scales = 4 * (3584 + 512 + 512 + 3584 + 18944 + 18944 + 3584)
    vectors = 2 * (3584 + 512 + 512 + 2 * 3584)
    head = 3584 * 152064 + 4 * 152064 + 2 * 3584
    assert bytes_model.weight_bytes(c) == 28 * (matrices + scales + vectors) + head
    assert 7.0e9 < bytes_model.weight_bytes(c) < 7.2e9
    assert bytes_model.kv_token_bytes(c) == 57_344
    floor = bytes_model.step_floor_bytes(c, resident_tokens=10_000)
    assert floor == bytes_model.weight_bytes(c) + 573_440_000


def test_bytes_of_the_72b_cut_by_hand():
    c = config("qwen25-72b-l8-int8")
    matrices = 2 * 8192 * 8192 + 2 * 8192 * 1024 + 3 * 8192 * 29568
    assert matrices == 877_658_112
    assert bytes_model.kv_token_bytes(c) == 32_768
    w = bytes_model.weight_bytes(c)
    assert 8 * matrices + 8192 * 152064 < w < 8 * matrices + 8192 * 152064 + 4e6


def test_peaks_know_the_v5e_and_refuse_the_rest():
    assert bytes_model.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        bytes_model.peaks("cpu")
    with pytest.raises(KeyError):
        bytes_model.peaks("source")


# -- trace reduction ------------------------------------------------------------
def synthetic_planes():
    ms = 1e6
    ops = [("fusion.1", 0 * ms, 4 * ms, {}), ("copy.7", 4 * ms, 2 * ms, {}),
           ("fusion.2", 10 * ms, 5 * ms, {}),
           ("transpose.3", 12 * ms, 1 * ms, {}),          # inside fusion.2
           ("fusion.9", 18 * ms, 2 * ms, {"hlo_category": "data formatting"})]
    host = [("engine.mixed_step_async", 5 * ms, 6 * ms, {}),
            ("engine.mixed_step_async", 14 * ms, 5 * ms, {}),
            ("engine.decode_block", 19.5 * ms, 0.5 * ms, {}),
            ("other", 0.0, 20 * ms, {})]
    return [("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", [])]),
            ("/host:CPU", [("python3", host)])]


def test_reduction_arithmetic_on_a_synthetic_trace():
    r = trace_reduce.reduce_planes(synthetic_planes(), chips=1)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.020)
    assert r["busy_s"] == pytest.approx(0.013)        # 0-6, 10-15, 18-20
    # fusion.2 holds transpose.3 inside it: own times add up to the busy time
    assert r["op_sum_s"] == pytest.approx(0.013)
    assert r["relayout_s"] == pytest.approx(0.005)    # copy, transpose, category
    assert r["annotations"] == {"engine.mixed_step_async": 2,
                                "engine.decode_block": 1}
    assert r["steps"] == 3
    gaps = dict(r["idle_gaps"])
    assert gaps["engine.mixed_step_async"] == pytest.approx(0.007)
    assert dict(r["device_ops"])["fusion.2"] == pytest.approx(0.004)


def test_a_trace_without_a_device_plane_reads_as_no_device():
    planes = [p for p in synthetic_planes() if not p[0].startswith("/device")]
    r = trace_reduce.reduce_planes(planes, chips=1)
    assert r["devices"] == 0 and r["busy_s"] == 0.0 and r["steps"] == 3


@pytest.mark.parametrize("name,expected", [
    ("copy.12", True), ("%copy-start.3", True), ("transpose.1", True),
    ("fusion.44", False), ("copy_fusion.2", True), ("convolution.9", False),
    ("bitcast_select_fusion.4", False), ("all-reduce.1", False),
    ("%copy.117 = bf16[114688,16,4,128]{3,1,2,0:T(8,128)(2,1)} copy(bf16[114688,16,4,128]{3,2,1,0} %p)", True),
    ("%while.36 = (s32[]{:T(128)}, bf16[32,128,3584]{2,1,0}) while((s32[]) %t), body=%copy_body", False),
])
def test_relayout_names(name, expected):
    assert trace_reduce.is_relayout(name) is expected


def test_device_operations_are_named_without_their_layouts():
    name = ("%fusion.318 = (f32[4,4,7,128]{3,2,1,0:T(8,128)S(1)}, f32[4,4,7,128,"
            "6144]{3,4,2,1,0:T(8,128)}) fusion(f32[4]{0} %x, bf16[2]{0} %y), kind=kLoop")
    assert trace_reduce.short_name(name) == (
        "fusion.318 (f32[4,4,7,128], f32[4,4,7,128,6144]) fusion")
    assert trace_reduce.short_name("copy.7") == "copy.7"
    assert len(trace_reduce.short_name("%a = " + "f32[1]" * 60 + " add()")) <= 96


def test_recorded_tpu_trace_reduces_to_sane_shares():
    path = os.path.join(HERE, "tiny_tpu.xplane.pb.gz")
    r = trace_reduce.reduce_file(path, chips=1)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["op_sum_s"] == pytest.approx(r["busy_s"], rel=1e-6)
    assert 0 <= r["relayout_s"] <= r["op_sum_s"]
    assert r["steps"] > 0 and r["device_ops"]
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    idle = sum(s for _, s in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)


# -- tokens ---------------------------------------------------------------------
def test_every_token_id_is_one_character_and_comes_back():
    ids = list(range(0, 152064, 7)) + [127, 128, 255, 256, 262, 55039, 55040, 152063]
    text = tokens.decode(ids)
    assert len(text) == len(ids)
    assert tokens.encode(text) == ids
    assert json.loads(json.dumps(text)) == text     # survives the wire
    assert tokens.decode(list(b"plain ascii {}")) == "plain ascii {}"


def test_template_is_the_programs_byte_template():
    from benchmarks.server import bench_tokenizer
    from opsagent_tpu.serving.chat_template import apply_chat_template

    messages = [{"role": "system", "content": "you are an agent"},
                {"role": "user", "content": "diagnose"},
                {"role": "assistant", "content": tokens.decode([300, 65, 200])},
                {"role": "user", "content": "observation"}]
    tok = bench_tokenizer(512)
    assert apply_chat_template(tok, messages) == tokens.template_ids(messages)
    assert tok.token_bytes(65) == b"A" and tok.token_bytes(300) == b""


# -- the constraint walker ------------------------------------------------------
def test_free_positions_are_the_insides_of_string_values():
    reply = '{"question":"ab\\"c","thought":"x\\u00e9y"}'
    free, illegal = check.free_positions([ord(c) for c in reply])
    picked = "".join(reply[i] for i in free)
    # a, b, the backslash, c, the closing quote; x, backslash, y, quote
    assert picked == 'ab\\c"x\\y"' and not illegal
    free, illegal = check.free_positions([ord("{"), 300, ord('"')])
    assert illegal == [1]


def test_verdict_prints_every_number_beside_its_limit():
    limits = {"min_checked_tokens": 10, "gap_max": 0.5, "gap_mean": 0.1}
    ok, lines = check.verdict(
        {"checked_tokens": 50, "illegal_tokens": 0, "gap_max": 0.2,
         "gap_mean": 0.01}, limits)
    assert ok and len(lines) == 5 and all("limit" in x for x in lines)
    bad, _ = check.verdict(
        {"checked_tokens": 50, "illegal_tokens": 0, "gap_max": 0.6,
         "gap_mean": 0.01}, limits)
    assert not bad
    none, _ = check.verdict({"checked_tokens": 0}, limits)
    assert not none


@pytest.mark.parametrize("impl,wrong", [
    ({"dtype": "bfloat16", "quantize": "int8", "kv_quantize": "none"}, []),
    ({"dtype": "bfloat16", "quantize": "int8", "kv_quantize": "int8"},
     ["kv_pages"]),
    ({"dtype": "bfloat16", "quantize": "int4", "kv_quantize": ""},
     ["weights"]),
    ({"dtype": "float32", "quantize": "", "kv_quantize": "none"},
     ["weights", "compute", "kv_pages"]),
])
def test_the_programs_own_report_is_held_to_the_stated_precision(impl, wrong):
    stated = config("qwen25-7b-int8")["precision"]
    lines = check.precision_mismatches(stated, impl)
    assert [line.split(":")[0] for line in lines] == wrong
    ok, _ = check.verdict(
        {"checked_tokens": 500, "illegal_tokens": 0, "gap_max": 0.0,
         "gap_mean": 0.0, "precision_mismatches": len(lines)},
        config("qwen25-7b-int8")["check"]["limits"])
    assert ok is (not wrong)


def test_sample_holds_the_longest_and_is_drawn_from_the_seed():
    finished = [{"prompt_ids": [1] * n, "reply_ids": [2] * 10} for n in range(5, 60)]
    a = check.select(finished, random.Random(3), 40, 6)
    b = check.select(finished, random.Random(3), 40, 6)
    c = check.select(finished, random.Random(4), 40, 6)
    assert a == b and a != c
    assert len(a[0]["prompt_ids"]) == 59 and len(a) == 4


def test_sample_takes_one_of_each_client_before_a_second_of_any():
    finished = [{"prompt_ids": [1] * (20 + i), "reply_ids": [2] * 10,
                 "client": i % 4} for i in range(24)]
    for seed in (1, 2, 3):
        picked = check.select(finished, random.Random(seed), 10**6, 4)
        assert sorted(s["client"] for s in picked) == [0, 1, 2, 3]
        assert len(picked[0]["prompt_ids"]) == 43
    eight = check.select(finished, random.Random(1), 10**6, 8)
    assert sorted(s["client"] for s in eight[:4]) == [0, 1, 2, 3]
    assert len(eight) == 8


@pytest.mark.parametrize("cell_config", ["qwen25-7b-int8", "qwen25-72b-l8-int8"])
def test_the_check_reads_the_same_whatever_the_block(cell_config):
    """The sample goes through the reference in blocks of sequences: the
    numbers are those of one block, and padding rows compare nothing."""
    tiny = load_data(os.path.join(
        ROOT, "benchmarks", "configs", cell_config + ".json"), rehearse=True)
    rng = random.Random(5)
    samples = [
        {"prompt_ids": [rng.randrange(32, 127) for _ in range(20 + 7 * i)],
         "reply_ids": [rng.randrange(32, 127) for _ in range(5 + i)],
         "constrained": False} for i in range(5)
    ]
    samples.append({"prompt_ids": [40] * 12, "constrained": True,
                    "reply_ids": [ord(c) for c in '{"thought":"ab"}']})
    got = []
    for block in (2, 6):
        got.append(check.run_check(tiny, 77, samples, control_bits=4,
                                   block=block))
    a, b = got
    assert a["checked_tokens"] == b["checked_tokens"] == sum(range(5, 10)) + 3
    assert a["requests"] == 6 and a["illegal_tokens"] == 0
    for key in ("gap_max", "gap_mean", "agree_share"):
        assert a[key] == pytest.approx(b[key], rel=1e-5, abs=1e-6)
        assert a["control"][key] == pytest.approx(
            b["control"][key], rel=1e-5, abs=1e-6)
    assert a["gap_max"] > 0.1      # random tokens are not the reference's


# -- the model-step readers -------------------------------------------------------
def test_a_fused_decode_block_counts_as_its_passes():
    counts = {"engine.mixed_step_async": 4, "engine.decode_block": 2,
              "engine.ffwd_step": 1, "engine.other_span": 9}
    assert trace_reduce.model_passes(counts, 8) == 4 + 16 + 1
    assert trace_reduce.model_passes({}, 8) == 0


@pytest.mark.parametrize("counts,passes", [
    ({"engine.mixed_step_async": 29}, 29),
    ({"engine.mixed_step_async": 5, "engine.decode_block": 3}, 29),
])
def test_step_readers_charge_time_and_bytes_by_the_pass(counts, passes):
    c = config("qwen25-72b-l8-int8")
    trace = {"devices": 1, "busy_s": 3.0, "window_s": 3.0, "op_sum_s": 3.0,
             "annotations": counts, "resident_tokens": 16000.0}
    ctx = {"trace": trace, "config": c, "device": {"kind": "TPU v5 lite"}}
    ms = load_module("layer_metrics", "step.device_ms_mean").read(ctx)
    assert ms == pytest.approx(3000.0 / passes)
    share = load_module("layer_metrics", "step.hbm_floor_share").read(ctx)
    floor_s = bytes_model.step_floor_bytes(c, 16000.0) / 819e9
    assert share == pytest.approx(100.0 * floor_s / (3.0 / passes))
    assert 0 < share < 100
    empty = dict(ctx, trace=dict(trace, annotations={}))
    assert load_module("layer_metrics", "step.device_ms_mean").read(empty) is None


def test_no_share_of_the_recorded_trace_passes_100():
    r = trace_reduce.reduce_file(
        os.path.join(HERE, "tiny_tpu.xplane.pb.gz"), chips=1)
    c = config("qwen25-7b-int8")
    ctx = {"trace": dict(r, resident_tokens=0.0), "config": c,
           "device": {"kind": "TPU v5 lite"}}
    assert trace_reduce.model_passes(r["annotations"], 8) >= r["steps"]
    for name in ("device.idle_share", "kernels.relayout_share"):
        value = load_module("layer_metrics", name).read(ctx)
        assert 0 <= value <= 100, name


# -- the generator ----------------------------------------------------------------
@pytest.mark.parametrize("mix", ["agent-turns", "long-generate"])
def test_same_seed_same_requests_other_seed_same_sizes(mix):
    params = traffic(mix)
    a, b = closed_sessions.plan(params, 11), closed_sessions.plan(params, 11)
    c = closed_sessions.plan(params, 2**31 + 12)
    assert a == b and a != c

    def sizes(p):
        return (sorted(s["first_user"] for s in p["sessions"]),
                sorted(x for s in p["sessions"] for x in s["max_tokens"]),
                sorted(x for s in p["sessions"] for x in s["observations"]))

    assert sizes(a) == sizes(c)
    first = params["first_user_tokens"]
    assert all(first["lo"] <= s["first_user"] <= first["hi"]
               for s in a["sessions"])
    assert len(a["system"]) == params["system_tokens"]


def test_a_sample_of_replies_with_little_free_grows_until_it_can_be_judged():
    """A seed whose model closes every string at once (cell 1, seed
    2600003303 on the chip, PR 26): three free positions a reply. The sample
    grows past ``max_requests`` in its own order until the comparison has
    its least tokens; a sample that has them is what it was."""
    closed = [ord(c) for c in '{"question":"","thought":"","action":""}']
    wordy = [ord(c) for c in '{"question":"how many pods","thought":"count"}']
    assert check.checkable({"reply_ids": closed, "constrained": True}) == 3
    assert check.checkable({"reply_ids": closed, "constrained": False}) == len(closed)

    def finished(reply):
        return [{"prompt_ids": [1] * (40 + i), "reply_ids": reply,
                 "constrained": True, "client": i % 8} for i in range(96)]

    old = check.select(finished(closed), random.Random(7), 10**6, 12)
    grown = check.select(finished(closed), random.Random(7), 10**6, 12, 100)
    assert len(old) == 12 and grown[:12] == old
    assert len(grown) == 34 and sum(map(check.checkable, grown)) >= 100
    # never past four times the requests, however little is free
    capped = check.select(finished(closed), random.Random(7), 10**6, 12, 10**6)
    assert len(capped) == 48 and capped[:34] == grown
    # replies with enough free positions: the sample does not change
    same = check.select(finished(wordy), random.Random(7), 10**6, 12, 100)
    assert same == check.select(finished(wordy), random.Random(7), 10**6, 12)
    assert sum(map(check.checkable, same)) >= 100
