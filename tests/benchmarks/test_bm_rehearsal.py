"""``benchmarks/run.py --rehearse`` end to end on the CPU, for every cell
that BENCHMARK.json names (later cells are covered without an edit here),
with the timed path broken underneath, and in a temporary copy that gains a
cell, a traffic mix and a per-layer metric from new files and entries only.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def cells() -> list[str]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def rehearse(root: str, workload: str, *extra: str) -> tuple[int, dict, str]:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", workload, "--seed", str(2**31 + 5), "--seconds", "3",
         "--rehearse", *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=900,
    )
    lines = proc.stdout.strip().splitlines()
    assert lines, proc.stderr[-2000:]
    try:
        last = json.loads(lines[-1])
    except ValueError:
        pytest.fail(f"last line is no JSON object:\n{proc.stdout[-3000:]}")
    return proc.returncode, last, proc.stdout


@pytest.mark.parametrize("workload", cells())
def test_every_cell_rehearses_on_the_cpu(workload):
    rc, last, out = rehearse(ROOT, workload, "--trace", "1")
    assert rc == 3, out[-3000:]
    assert last["correct"] is True, out[-3000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    assert last["metrics"] == {} and "breakdown" not in last
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    wanted = {m["name"] for m in bench["end_to_end"]
              if workload in m.get("workloads", [workload])}
    assert set(last["rehearsal"]["end_to_end_seen"]) == wanted
    assert "compared gap_max" in out and "limit" in out
    # a per-layer metric is read where the metric it moves is reported
    moved = {m["name"] for m in bench["per_layer"]
             if m["moves"] in wanted and workload in m.get("workloads", [workload])}
    seen = set(last["rehearsal"]["per_layer_seen"])
    assert seen <= moved and "engine.tokens_per_dispatch" in seen


def test_a_broken_timed_path_comes_out_not_correct():
    rc, last, out = rehearse(ROOT, cells()[-1], "--break-every", "9")
    assert rc == 3, out[-3000:]
    assert last["correct"] is False, out[-3000:]
    assert "NOT MET" in out


def test_the_programs_own_int8_pages_run_as_a_control():
    """``--engine kv_quantize=int8`` puts the program's lower-precision
    path in a cell's place. Its served tokens sit inside the limits (here
    as on the chip, PERF.md section 2), so ``correct`` comes out false by
    the program's own report against the stated precision."""
    rc, last, out = rehearse(ROOT, cells()[-1], "--engine", "kv_quantize=int8")
    assert rc == 3, out[-3000:]
    assert "CONTROL RUN" in out and '"kv_quantize": "int8"' in out
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["correct"] is False
    assert "precision not as stated: kv_pages: stated float32" in out
    assert "compared precision_mismatches: 1 (limit <= 0) NOT MET" in out


def test_no_tpu_means_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cells()[0], "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")


def test_a_new_cell_and_metric_need_only_new_files_and_entries(tmp_path):
    root = str(tmp_path / "copy")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench_dir = os.path.join(root, "benchmarks")
    with open(os.path.join(bench_dir, "traffic", "long-generate.json")) as f:
        mix = json.load(f)
    mix["rehearsal"]["sessions"] = 3
    with open(os.path.join(bench_dir, "traffic", "throw-away.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench_dir, "layer_metrics", "tmp.finished.py"), "w") as f:
        f.write('"""Layer: scheduler. Source: client counts. '
                'Moves: tpot_p50_ms."""\n\n\n'
                "def read(ctx):\n    return float(ctx['counts']['attempted'])\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({
        "name": "tmp.throw-away", "config": bench["configs"][0]["name"],
        "traffic": "throw-away", "chips": 1, "why": "extension test"})
    bench["per_layer"].append({
        "name": "tmp.finished", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "tpot_p50_ms", "workloads": ["tmp.throw-away"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    rc, last, out = rehearse(root, "tmp.throw-away", "--trace", "1")
    assert rc == 3 and last["correct"] is True, out[-3000:]
    assert "tmp.finished" in last["rehearsal"]["per_layer_seen"]
