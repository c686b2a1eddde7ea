"""chip_smoke.py off the chip: it must fail, say nothing that reads as a
result, and never run something smaller or slower in the chip's place.
(What it proves ON the chip is the builder's and the driver's run.)"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, env_extra, cwd=REPO, script=SMOKE, timeout=600):
    env = dict(os.environ)
    for k, v in env_extra.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return subprocess.run(
        [sys.executable, script, *args], capture_output=True, text=True,
        timeout=timeout, env=env, cwd=cwd,
    )


def _no_result(out) -> None:
    assert out.returncode != 0, out.stdout[-2000:]
    assert '"ok"' not in out.stdout, out.stdout[-2000:]
    assert '"ok"' not in out.stderr


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_without_a_tpu_it_exits_at_once(args):
    """JAX held to the CPU: non-zero at once — no server started, no
    smaller model, no other backend."""
    out = _run(args, {"JAX_PLATFORMS": "cpu"}, timeout=60)
    _no_result(out)
    assert "no TPU" in out.stdout
    assert "starting:" not in out.stdout and "engine up" not in out.stdout


def test_alone_in_a_directory_it_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo there is no program to start: non-zero, no result."""
    script = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = _run(
        [], {"JAX_PLATFORMS": None, "PYTHONPATH": None}, cwd=tmp_path,
        script=str(script), timeout=120,
    )
    _no_result(out)
    assert "FAILED" in out.stdout


def test_four_chip_rehearsal_runs_and_never_succeeds():
    """--rehearse walks the tp comparison on virtual CPU devices at
    tiny-test, and ALWAYS exits non-zero without the result line."""
    out = _run(
        ["--chips", "4", "--rehearse"],
        {"JAX_PLATFORMS": "cpu",
         "XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
        timeout=300,
    )
    _no_result(out)
    assert out.returncode == 3, (out.stdout + out.stderr)[-2000:]
    assert "rehearsal complete" in out.stdout
    assert "has shards on 2 devices" in out.stdout


def test_one_chip_rehearsal_runs_and_never_succeeds():
    """The whole server flow at tiny-test on the CPU (minutes: in the
    slow lane): every phase holds, and the exit is still non-zero."""
    out = _run(["--rehearse"], {"JAX_PLATFORMS": "cpu"}, timeout=900)
    _no_result(out)
    assert out.returncode == 3, (out.stdout + out.stderr)[-3000:]
    for phase in (
        "the repeated request gives the identical text",
        "the streamed text equals the plain one",
        "conforms to the ToolPrompt schema",
        "mixed ticks carried decode rows AND prefill chunks",
        "zero post-warmup compiles",
    ):
        assert phase in out.stdout, phase
