"""``benchmarks/run.py --rehearse`` end to end on the CPU, for every cell
that BENCHMARK.json names (later cells are covered without an edit here),
with the timed path broken underneath, in a temporary copy that gains a
cell, a traffic mix and a per-layer metric from new files and entries only,
and in one that gains a configuration of another model family so.
"""

import filecmp
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
    return proc.returncode, last, proc.stdout + "\n-- stderr --\n" + proc.stderr


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
    # each number compared beside its limit: the result line's last key, and
    # the last lines of standard error
    assert list(last)[-1] == "compared"
    assert {"checked_tokens", "illegal_tokens", "precision_mismatches",
            "gap_max", "gap_mean"} == set(last["compared"])
    assert all({"value", "limit"} == set(x) for x in last["compared"].values())
    assert out.rstrip().splitlines()[-1].startswith("[bench] compared gap_mean")
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


def copy_of_the_benchmark(tmp_path) -> str:
    root = str(tmp_path / "copy")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


def test_a_new_cell_and_metric_need_only_new_files_and_entries(tmp_path):
    root = copy_of_the_benchmark(tmp_path)
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


# -- another model family, from new files and entries only -----------------------
FIXTURE = os.path.join(ROOT, "tests", "benchmarks", "fixture_family")


@pytest.fixture(scope="module")
def copy_with_another_family(tmp_path_factory):
    """A copy of the benchmark that gains a throw-away family (latent
    attention with a low-rank query and a decoupled rotary part, the pages
    holding the latent; a leading dense layer, then routed and shared
    experts behind a float32 sigmoid router): a configuration's file, a
    family module, a plain reference, one ``configs`` entry and one cell on
    the ``long-generate`` mix, and nothing else."""
    root = copy_of_the_benchmark(tmp_path_factory.mktemp("family"))
    added = []
    for kind in ("configs", "families", "reference"):
        for name in sorted(os.listdir(os.path.join(FIXTURE, kind))):
            if name.endswith((".json", ".py")):
                target = os.path.join(root, "benchmarks", kind, name)
                assert not os.path.exists(target), "a new file, not an edit"
                shutil.copy(os.path.join(FIXTURE, kind, name), target)
                added.append(os.path.join(kind, name))
    assert len(added) == 3
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-latent-experts", "source": "tests/benchmarks",
        "file": "benchmarks/configs/tiny-latent-experts.json", "reduced": [],
        "why": "extension test: latent attention, routed and shared experts"})
    bench["workloads"].append({
        "name": "tmp.latent-experts", "config": "tiny-latent-experts",
        "traffic": "long-generate", "chips": 1, "why": "extension test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root, added


def test_another_family_needs_only_new_files_and_entries(copy_with_another_family):
    root, added = copy_with_another_family
    # no file the benchmark already had differs, and BENCHMARK.json differs
    # by added entries only
    def differing(a: str, b: str) -> list[str]:
        cmp = filecmp.dircmp(a, b, ignore=["__pycache__"])
        out = [os.path.join(a, n) for n in cmp.diff_files + cmp.left_only]
        for sub in cmp.common_dirs:
            out += differing(os.path.join(a, sub), os.path.join(b, sub))
        return out

    assert not differing(os.path.join(ROOT, "benchmarks"),
                         os.path.join(root, "benchmarks"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        ours = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        theirs = json.load(f)
    for key, value in ours.items():
        if isinstance(value, list) and key not in ("command", "paths"):
            assert theirs[key][:len(value)] == value, key
        else:
            assert theirs[key] == value, key

    rc, last, out = rehearse(root, "tmp.latent-experts", "--trace", "1",
                             "--control-bits", "4")
    assert rc == 3 and last["correct"] is True, out[-3000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert "engine.tokens_per_dispatch" in last["rehearsal"]["per_layer_seen"]
    # the traced run says what a trace of new scope names needs
    assert "compiles fresh" in out
    # the program served latent pages, and its tokens sit on the reference
    line = next(x for x in out.splitlines() if "reference check:" in x)
    numbers = json.loads(line.split("reference check: ", 1)[1])
    assert numbers["checked_tokens"] >= 40 and numbers["agree_share"] == 1.0
    # the control (the reference at int4) does not
    assert numbers["control"]["gap_max"] > 0.1 > numbers["gap_max"]


def test_another_familys_broken_path_comes_out_not_correct(copy_with_another_family):
    root, _added = copy_with_another_family
    rc, last, out = rehearse(root, "tmp.latent-experts", "--break-every", "9")
    assert rc == 3, out[-3000:]
    assert last["correct"] is False, out[-3000:]
    assert "NOT MET" in out
