"""Full-circle capability test: train -> checkpoint -> serve -> agent.

Runs scripts/train_tiny_agent.py end to end: the in-tree train step
fine-tunes the tiny model on ReAct transcripts (generated with the same
serialization code the live loop uses), saves an HF-format safetensors
checkpoint, boots the serving engine from that file, and the REAL agent
loop — tpu:// provider, FSM-constrained decoding, kubectl replay tool —
must produce the correct tool call and final answer from the trained
weights. This is the in-tree replacement for the capability the reference
buys from GPT-4 (reference pkg/handlers/execute.go:205), demonstrated
with actual learned weights rather than canned LLM replies.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_train_serve_agent_roundtrip(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [
            sys.executable, "-u",
            os.path.join(REPO, "scripts", "train_tiny_agent.py"),
            "--steps", "600",
            # Extra SERVING passes (training happens once): the same
            # checkpoint re-served under each quantized configuration
            # must reproduce every memorized assertion — greedy
            # faithfulness on LEARNED weights, not random ones. int8 KV
            # and int8 weights gate on the answers; int4 gates on greedy
            # prefix agreement vs fp32 (tiny-test's 64-wide contractions
            # are group-wise int4's worst case, so flipped ANSWERS are
            # expected signal there — but agreement ~0 means a
            # packing/dequant bug and fails the run).
            "--serve-variants", "kv-int8,int8,int4",
            "--out", str(tmp_path / "ckpt"),
        ],
        capture_output=True, text=True, timeout=1800, env=env, cwd=REPO,
    )
    assert out.returncode == 0, (out.stdout + out.stderr)[-3000:]
    assert "agent PASSED" in out.stdout
    assert "[kv-int8]" in out.stderr and "[int8]" in out.stderr
    # int4 ran AND its quantitative gate reported (a floor breach would
    # have failed the returncode assertion above).
    assert "greedy prefix agreement vs fp32" in out.stderr
    assert (tmp_path / "ckpt" / "model.safetensors").exists()


@pytest.mark.slow
def test_train_serve_agent_multi_task(tmp_path):
    """The 7-instruction corpus (5 kubectl episodes + 1 python-tool
    episode + 1 jq episode) trains to memorization and the served agent
    answers EVERY instruction correctly through the real loop — tool
    dispatch across three tools, FSM-constrained decode, replay
    cluster."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [
            sys.executable, "-u",
            os.path.join(REPO, "scripts", "train_tiny_agent.py"),
            "--tasks", "multi",
            "--steps", "3000",
            "--no-probe",  # held-out probes are demo-only wall clock
            "--out", str(tmp_path / "ckpt"),
        ],
        capture_output=True, text=True, timeout=2400, env=env, cwd=REPO,
    )
    assert out.returncode == 0, (out.stdout + out.stderr)[-3000:]
    assert "agent PASSED (7 tasks)" in out.stdout


def test_multi_task_corpus_valid_under_fsm(tmp_path, monkeypatch):
    """Every multi-task training target must be reachable under the
    ToolPrompt FSM the serving path enforces, and every task's
    observation must match what the REAL tool functions return against
    the replay cluster — the same post-processed strings (noise filter,
    strip, venv interpreter) the agent loop marshals into turn 2, so any
    drift fails here in seconds instead of in the slow e2e run."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        from train_tiny_agent import (
            TASKS_MULTI,
            build_convs,
            train_phrasings,
        )
    finally:
        sys.path.remove(os.path.join(REPO, "scripts"))

    from opsagent_tpu.serving.constrained import (
        TOOLPROMPT_SCHEMA,
        json_constraint,
    )
    from opsagent_tpu.serving.tokenizer import ByteTokenizer
    from opsagent_tpu.tools.jq import jq
    from opsagent_tpu.tools.kubectl import kubectl
    from opsagent_tpu.tools.python_tool import python_repl
    from opsagent_tpu.tools.replay import (
        MULTI_TASK_SCRIPT,
        install_replay_kubectl,
    )

    convs = build_convs(TASKS_MULTI)
    # Two convs per TRAINED phrasing (base instruction + all but the
    # held-out alternative): 7 tasks x 4 phrasings x 2 turns.
    assert len(convs) == 2 * sum(
        len(train_phrasings(t)) for t in TASKS_MULTI
    ) == 56
    con = json_constraint(ByteTokenizer(vocab_size=512), TOOLPROMPT_SCHEMA)
    for _, reply in convs:
        dfa = con.fsm.dfa
        state = dfa.run(dfa.start, reply.encode())
        assert state >= 0 and dfa.accept[state], reply

    # monkeypatch records PATH so teardown restores it even though
    # install_replay_kubectl mutates os.environ directly (same pattern
    # as test_real_checkpoint.py's replay fixture).
    monkeypatch.setenv("PATH", os.environ["PATH"])
    install_replay_kubectl(MULTI_TASK_SCRIPT, str(tmp_path / "bin"))
    tools = {"kubectl": kubectl, "python": python_repl, "jq": jq}
    for t in TASKS_MULTI:
        got = tools[t["tool"]](t["tool_input"])
        assert got == t["observation"], (t["tool_input"], got)
