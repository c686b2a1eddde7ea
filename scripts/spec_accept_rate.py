#!/usr/bin/env python
"""Measure the speculative-decoding accept rate on the AGENT workload.

VERDICT r03 #3: prompt-lookup speculation has shipped dormant
(``EngineConfig.speculative_k = 0``) for two rounds because the decision
needs an accept-rate measurement on trained weights re-emitting ReAct
JSON scaffolding — random weights accept ~nothing, so bench stage 4 only
bounds the overhead. This script closes the question:

1. train the tiny in-tree agent model (scripts/train_tiny_agent.py's
   corpus/recipe — real trained weights whose replies repeat the
   ToolPrompt JSON structure already present in the prompt, exactly the
   n-gram-lookup-friendly shape of the production agent loop);
2. run the SAME two-turn agent loop with speculative_k=0 and k=4 over
   fresh engines (greedy, FSM off so speculation engages);
3. report: accept rate (a model/workload property that transfers to
   TPU), decode dispatches per generated token (the host-RTT amortizer
   speculation buys), and wall-clock delta (CPU-only, indicative).

Accept rate is read from the ``engine.spec_step_tokens`` metric: each
live verify step emits 1 + (accepted drafts) tokens, so
``(mean - 1) / k`` is the per-draft accept rate.

Run: python scripts/spec_accept_rate.py [--steps 800] [--k 4]
Prints one JSON line with the measurements.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp  # noqa: E402


def run_loop(ckpt: str, tok_path: str, cfg, k: int) -> dict:
    """The agent conversation's two turns against a fresh engine.

    Driven through ``chat_completion`` directly (NOT the ReAct loop):
    against tpu:// targets the loop turns on FSM-constrained decoding,
    which disables speculation by design (engine.py gates "spec" on
    fsm_obj is None) — the measurement needs the same prompts/replies
    WITHOUT the FSM, and the trained model emits valid ToolPrompt JSON
    unconstrained."""
    from opsagent_tpu.serving import api as serving_api
    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.tools import ToolPrompt
    from opsagent_tpu.utils.perf import get_perf_stats
    from scripts.train_tiny_agent import build_convs

    engine = Engine(
        EngineConfig(
            model="tiny-test",
            checkpoint=ckpt,
            tokenizer=tok_path,
            dtype=jnp.float32,
            num_pages=512,
            page_size=16,
            max_pages_per_seq=64,
            max_batch_size=2,
            prefill_buckets=(128, 512, 1024),
            speculative_k=k,
        ),
        model_cfg=cfg,
    )
    stack = serving_api.ServingStack(engine)
    perf = get_perf_stats()
    perf.reset()
    try:
        # The exact two agent turns (turn 2's user message marshals the
        # observation back as ToolPrompt JSON — the n-gram-rich shape).
        convs = build_convs()
        t0 = time.perf_counter()
        final = ""
        for messages, _expected in convs:
            resp = stack.chat_completion({
                "messages": messages,
                "max_tokens": 256,
                "temperature": 0.0,
            })
            reply = resp["choices"][0]["message"]["content"] or ""
            try:
                final = ToolPrompt.from_json(reply).final_answer or final
            except ValueError:
                pass
        wall = time.perf_counter() - t0
        ok = "3" in final and "namespace" in final.lower()
        stats = perf.get_stats()
        tokens = stats.get("engine.decode_tokens", {})
        dispatch = stats.get("engine.block_dispatch", {})
        spec = stats.get("engine.spec_step_tokens", {})
        produced = tokens.get("sum", 0) or (
            tokens.get("avg", 0) * tokens.get("count", 0)
        )
        return {
            "k": k,
            "ok": ok,
            "wall_s": round(wall, 2),
            "tokens": int(produced),
            "dispatches": int(dispatch.get("count", 0)),
            "spec_steps": int(spec.get("count", 0)),
            "tokens_per_verify_step": round(spec.get("avg", 0.0), 3),
            "accept_rate": (
                round((spec.get("avg", 1.0) - 1.0) / k, 3) if k else None
            ),
        }
    finally:
        stack.close()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=800)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    from scripts.train_tiny_agent import pin_demo_platform

    pin_demo_platform()  # CPU unless OPSAGENT_DEMO_PLATFORM says otherwise

    # Train (or reuse) the tiny agent checkpoint via the demo's recipe.
    import subprocess
    import tempfile

    out = args.out or tempfile.mkdtemp(prefix="opsagent-specrate-")
    ckpt = os.path.join(out, "model.safetensors")
    if not os.path.exists(ckpt):
        rc = subprocess.run(
            [sys.executable, os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "train_tiny_agent.py",
            ), "--steps", str(args.steps), "--out", out, "--skip-agent"],
        ).returncode
        if rc:
            print(f"training failed rc={rc}", file=sys.stderr)
            return rc

    import dataclasses

    from opsagent_tpu.models.config import get_config_preset
    from opsagent_tpu.serving.tokenizer import load_tokenizer

    tok_path = os.path.join(out, "tokenizer")
    cfg = get_config_preset("tiny-test")
    if os.path.isdir(tok_path):
        cfg = dataclasses.replace(
            cfg, vocab_size=load_tokenizer(tok_path).vocab_size
        )
    else:
        tok_path = ""

    base = run_loop(ckpt, tok_path, cfg, k=0)
    spec = run_loop(ckpt, tok_path, cfg, k=args.k)
    result = {
        "baseline": base,
        "speculative": spec,
        "dispatch_reduction": (
            round(1.0 - spec["dispatches"] / base["dispatches"], 3)
            if base["dispatches"] else None
        ),
        "platform": jax.devices()[0].platform,
    }
    print(json.dumps(result), flush=True)
    return 0 if (base["ok"] and spec["ok"]) else 1


if __name__ == "__main__":
    sys.exit(main())
