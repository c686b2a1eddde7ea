#!/usr/bin/env python
"""Train a tiny in-tree model to BE the ops agent, then serve it.

The full-circle demo the reference cannot do (its "model" is a remote
GPT-4 call, reference pkg/handlers/execute.go:205): using only this
framework —

1. generate ReAct transcripts in the exact wire format the agent loop
   speaks (ToolPrompt JSON in/out, observation-marshaled-as-user-message,
   byte-tokenizer chat template — the same code paths serving uses);
2. fine-tune the tiny llama-family model on them with the in-tree
   sharded train step (training/trainer.py) until it memorizes the
   tool-calling behavior;
3. save an HF-format safetensors checkpoint (models/loader.py);
4. boot the serving engine FROM THAT CHECKPOINT and run the real agent
   loop against it (tpu:// provider, FSM-constrained decoding, kubectl
   replay tool);
5. verify the agent answers the instruction correctly from trained
   weights.

Run: python scripts/train_tiny_agent.py [--steps 800] [--out DIR]
Exits 0 iff the served agent produces the expected final answer.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SYS_PROMPT = (
    "You are a k8s ops agent. Reply with ToolPrompt JSON; use the kubectl "
    "tool, then give final_answer."
)
INSTRUCTION = "count namespaces"
KUBECTL_CMD = "kubectl get namespaces --no-headers | wc -l"
FINAL_ANSWER = "There are 3 namespaces in the cluster."

# Each task: one two-turn ReAct episode (tool call -> observation ->
# final answer). ``observation`` must match BYTE-EXACTLY what the replay
# tool emits at serve time (tools/replay.py MULTI_TASK_SCRIPT), or the
# served turn-2 prompt diverges from the trained one. Optional
# ``phrasings`` lists alternative instruction wordings: all but the last
# train (same episode, different question), the LAST is HELD OUT and
# evaluated to probe phrasing robustness beyond memorization.
TASKS_SINGLE = [dict(
    instruction=INSTRUCTION,
    tool="kubectl", tool_input=KUBECTL_CMD, observation="3",
    thought1="I will count namespaces with kubectl.",
    thought2="The observation shows 3 namespaces.",
    obs2="The cluster has 3 namespaces.",
    final=FINAL_ANSWER,
)]

TASKS_MULTI = [dict(
    TASKS_SINGLE[0],
    phrasings=["how many namespaces are there",
               "count the namespaces in the cluster",
               "give me the namespace count",
               "tell me the number of namespaces"],
)] + [
    dict(
        instruction="which pods are crashing",
        phrasings=["list the crashing pods",
                   "find pods stuck in a crash loop",
                   "which pods keep restarting and crashing",
                   "show me pods that keep crashing"],
        tool="kubectl",
        tool_input="kubectl get pods -A | grep CrashLoopBackOff",
        observation="web-2   CrashLoopBackOff",
        thought1="I will grep pod listings for crash loops.",
        thought2="One pod is in CrashLoopBackOff.",
        obs2="web-2 is crash-looping.",
        final="Pod web-2 is in CrashLoopBackOff.",
    ),
    dict(
        instruction="how many nodes are ready",
        phrasings=["count the ready nodes",
                   "how many nodes report ready",
                   "number of nodes in the ready state",
                   "what is the ready node count"],
        tool="kubectl",
        tool_input="kubectl get nodes --no-headers | grep -cw Ready",
        observation="2",
        thought1="I will count Ready nodes with kubectl.",
        thought2="Two nodes report Ready.",
        obs2="2 nodes are Ready.",
        final="2 of the 3 nodes are Ready.",
    ),
    dict(
        instruction="what kubernetes version is the cluster running",
        phrasings=["which k8s version is installed",
                   "what version of kubernetes is this",
                   "tell me the kubernetes server version",
                   "report the cluster version"],
        tool="kubectl",
        tool_input="kubectl version --short",
        observation="Server Version: v1.29.3",
        thought1="I will ask kubectl for the server version.",
        thought2="The server reports its version.",
        obs2="Server version v1.29.3.",
        final="The cluster runs Kubernetes v1.29.3.",
    ),
    dict(
        instruction="how many pods run in the default namespace",
        phrasings=["count pods in the default namespace",
                   "number of pods in namespace default",
                   "how many pods are running in default",
                   "how many pods does default have"],
        tool="kubectl",
        tool_input="kubectl get pods -n default --no-headers | wc -l",
        observation="2",
        thought1="I will count pods in default with kubectl.",
        thought2="There are two pods in default.",
        obs2="2 pods in default.",
        final="There are 2 pods in the default namespace.",
    ),
    dict(
        # Third tool family (jq): the input embeds JSON-in-a-string —
        # the hardest wire shape the FSM-constrained decode must emit
        # byte-exactly (nested quotes escape through two JSON layers).
        instruction="extract the first item name from the status json",
        phrasings=["pull the first item's name out of the status json",
                   "use jq to get the first item name from the status json",
                   "what is the first item's name in the status json",
                   "read the first item name from the status json with jq"],
        tool="jq",
        tool_input='{"items":[{"name":"web-2","status":'
                   '"CrashLoopBackOff"}]} | .items[0].name',
        observation='"web-2"',
        thought1="I will extract the name with the jq tool.",
        thought2="The first item is named web-2.",
        obs2="The first item is web-2.",
        final="The first item in the status json is web-2.",
    ),
    dict(
        instruction="compute 6*7 using python",
        phrasings=["use python to compute 6*7",
                   "run python to calculate 6*7",
                   "calculate 6*7 with the python tool",
                   "what is 6*7, computed with python"],
        tool="python",
        tool_input="print(6*7)",
        observation="42",
        thought1="I will run the expression with the python tool.",
        thought2="The script printed 42.",
        obs2="The result is 42.",
        # >= 10 chars: the loop's template heuristic (react.py
        # is_template_value, reference simple.go:624-657) rejects
        # implausibly short finals like "6*7 = 42.".
        final="The result of 6*7 is 42.",
    ),
]


def train_phrasings(t) -> list[str]:
    """Instruction wordings that TRAIN: the base instruction plus all but
    the last alternative (the last is held out for the robustness probe)."""
    return [t["instruction"], *t.get("phrasings", [])[:-1]]


def heldout_phrasing(t) -> str | None:
    phr = t.get("phrasings", [])
    return phr[-1] if phr else None


def build_convs(tasks=None):
    """Two agent turns per task PER TRAINED PHRASING, serialized with the
    live loop's own wire code (tools.ToolPrompt) — (messages, target
    reply) pairs. The question field carries the phrasing, so the model
    learns the instruction -> episode mapping across wordings."""
    from opsagent_tpu.tools import ToolAction, ToolPrompt

    convs = []
    for t in tasks or TASKS_SINGLE:
        for phrasing in train_phrasings(t):
            user1 = f"Here are the instructions: {phrasing}"
            tp1 = ToolPrompt(
                question=phrasing,
                thought=t["thought1"],
                action=ToolAction(name=t["tool"], input=t["tool_input"]),
            )
            reply1 = tp1.to_json()

            # Turn 2's user message is EXACTLY what the loop marshals
            # back: the turn-1 ToolPrompt with the observation filled in
            # (react.py:193-194).
            tp1_obs = ToolPrompt(
                question=tp1.question, thought=tp1.thought,
                action=tp1.action, observation=t["observation"],
            )
            tp2 = ToolPrompt(
                question=phrasing,
                thought=t["thought2"],
                observation=t["obs2"],
                final_answer=t["final"],
            )
            reply2 = tp2.to_json()

            convs += [
                ([{"role": "system", "content": SYS_PROMPT},
                  {"role": "user", "content": user1}], reply1),
                ([{"role": "system", "content": SYS_PROMPT},
                  {"role": "user", "content": user1},
                  {"role": "assistant", "content": reply1},
                  {"role": "user", "content": tp1_obs.to_json()}], reply2),
            ]
    return convs


def train_bpe_tokenizer(out_dir: str, extra_corpus: tuple[str, ...] = (),
                        vocab_size: int = 512, tasks=None) -> str:
    """Train a REAL byte-level-BPE tokenizer (HF fast-tokenizer format)
    on the agent corpus and save it loadable via AutoTokenizer — the demo
    then exercises the same HFTokenizer path real checkpoints use, not
    the byte fallback. ``extra_corpus`` adds more training text (e.g. the
    full ReAct system prompt, so long prompts compress instead of
    exploding to near-byte token counts). Returns the tokenizer dir."""
    import json as jsonlib

    from tokenizers import Tokenizer, decoders, models, pre_tokenizers, trainers

    from opsagent_tpu.serving.chat_template import render_llama3

    corpus = list(extra_corpus)
    for messages, reply in build_convs(tasks):
        corpus.append(render_llama3(messages))
        corpus.append(reply)
    tok = Tokenizer(models.BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=vocab_size, special_tokens=["<bos>", "<eos>", "<pad>"],
        show_progress=False,
        # Full byte alphabet: without it, bytes absent from the tiny
        # corpus would be silently DROPPED at encode time (unk is None),
        # so any later prompt/observation edit could train on a lossy
        # target that the string-level FSM check cannot catch.
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
    )
    tok.train_from_iterator(corpus, trainer)
    tok_dir = os.path.join(out_dir, "tokenizer")
    os.makedirs(tok_dir, exist_ok=True)
    tok.save(os.path.join(tok_dir, "tokenizer.json"))
    with open(os.path.join(tok_dir, "tokenizer_config.json"), "w",
              encoding="utf-8") as f:
        jsonlib.dump({
            "tokenizer_class": "PreTrainedTokenizerFast",
            "bos_token": "<bos>", "eos_token": "<eos>", "pad_token": "<pad>",
        }, f)
    return tok_dir


def build_dataset(tok, tasks=None):
    """(token_ids, loss_mask) rows: prompts rendered by the SAME
    apply_chat_template the serving stack uses, targets validated
    reachable under the ToolPrompt FSM the serving path enforces."""
    from opsagent_tpu.serving.chat_template import apply_chat_template
    from opsagent_tpu.serving.constrained import (
        TOOLPROMPT_SCHEMA,
        json_constraint,
    )

    convs = build_convs(tasks)
    con = json_constraint(tok, TOOLPROMPT_SCHEMA)
    for _, reply in convs:
        dfa = con.fsm.dfa
        state = dfa.run(dfa.start, reply.encode())
        assert state >= 0 and dfa.accept[state], (
            f"FSM rejects training target: {reply!r}"
        )

    rows = []
    for messages, reply in convs:
        prompt_ids = apply_chat_template(tok, messages)
        reply_ids = tok.encode(reply) + [tok.eos_id]
        ids = prompt_ids + reply_ids
        mask = [0.0] * len(prompt_ids) + [1.0] * len(reply_ids)
        rows.append((ids, mask))
    return rows


def train_checkpoint(out_dir, steps=600, target_loss=0.01, lr=3e-3,
                     tasks=None):
    """Programmatic train-to-memorization for callers that need a tiny
    agent checkpoint in-process (the agent-conveyor bench stage, the
    conveyor e2e test): the same tiny-test + BPE recipe as ``main()``,
    minus the CLI/serve scaffolding. Falls back to the byte tokenizer
    when the ``tokenizers`` package is absent. Returns
    ``(ckpt_path, tok_path, model_cfg, final_loss, train_s)`` with
    ``tok_path == ""`` on the byte-tokenizer fallback."""
    import dataclasses

    from opsagent_tpu.models.config import get_config_preset
    from opsagent_tpu.models.loader import save_checkpoint
    from opsagent_tpu.parallel.mesh import make_mesh
    from opsagent_tpu.training import (
        TrainConfig,
        init_train_state,
        make_train_step,
    )

    tasks = tasks or TASKS_SINGLE
    cfg = get_config_preset("tiny-test")
    try:
        from opsagent_tpu.serving.tokenizer import load_tokenizer

        tok_path = train_bpe_tokenizer(out_dir, tasks=tasks)
        tok = load_tokenizer(tok_path)
        cfg = dataclasses.replace(cfg, vocab_size=tok.vocab_size)
    except ImportError:
        from opsagent_tpu.serving.tokenizer import ByteTokenizer

        tok_path = ""
        tok = ByteTokenizer(vocab_size=cfg.vocab_size)
    rows = build_dataset(tok, tasks)
    S = 8 * ((max(len(ids) for ids, _ in rows) + 7) // 8)
    tokens = np.full((len(rows), S), tok.pad_id, np.int32)
    mask = np.zeros((len(rows), S), np.float32)
    for i, (ids, m) in enumerate(rows):
        tokens[i, :len(ids)] = ids
        mask[i, :len(m)] = m
    mesh = make_mesh(tp=1, dp=1, sp=1, devices=jax.devices()[:1])
    tc = TrainConfig(learning_rate=lr, weight_decay=0.0, remat=False)
    params, opt_state = init_train_state(
        cfg, tc, mesh, jax.random.PRNGKey(0), dtype=jnp.float32
    )
    train_step = make_train_step(cfg, tc, mesh, dtype=jnp.float32)
    tokens_j, mask_j = jnp.asarray(tokens), jnp.asarray(mask)
    t0 = time.perf_counter()
    loss = float("inf")
    for i in range(steps):
        params, opt_state, tmetrics = train_step(
            params, opt_state, tokens_j, mask_j
        )
        if i % 50 == 0 or i == steps - 1:
            loss = float(tmetrics["loss"])
            if loss < target_loss:
                break
    train_s = time.perf_counter() - t0
    ckpt = os.path.join(out_dir, "model.safetensors")
    save_checkpoint(ckpt, params)
    return ckpt, tok_path, cfg, loss, train_s


def pin_demo_platform() -> None:
    """Run as a script, the demo trains and serves on the CPU, so that
    its transcript is deterministic (the tests compare it);
    OPSAGENT_DEMO_PLATFORM=tpu runs it on a chip. Importers (tests,
    bench.py) keep their own platform: nothing is pinned at import."""
    platform = os.environ.get("OPSAGENT_DEMO_PLATFORM", "cpu")
    os.environ["JAX_PLATFORMS"] = platform
    jax.config.update("jax_platforms", platform)


def main() -> int:
    pin_demo_platform()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=800)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--target-loss", type=float, default=0.01)
    ap.add_argument("--out", default="")
    ap.add_argument("--tokenizer", default="bpe", choices=("bpe", "byte"),
                    help="bpe = train a real HF fast tokenizer (the path "
                         "real checkpoints use); byte = the test fallback")
    ap.add_argument("--skip-agent", action="store_true",
                    help="train + save only (no serving run)")
    ap.add_argument("--tasks", default="single", choices=("single", "multi"),
                    help="single = the original count-namespaces episode; "
                         "multi = 6 instructions across kubectl AND the "
                         "python tool (pods/nodes/version/arithmetic), "
                         "each served and checked after training")
    ap.add_argument("--no-probe", action="store_true",
                    help="skip the non-gating held-out-phrasing probes "
                         "(each burns a full agent episode; CI uses this)")
    ap.add_argument("--serve-variants", default="",
                    help="comma list of extra serving configurations to "
                         "re-run the assertions under from the SAME "
                         "checkpoint: kv-int8 (int8 KV cache), int8 "
                         "(weight-only int8), int4 (weight-only int4, "
                         "gated on greedy agreement, see --int4-floor)")
    ap.add_argument("--int4-floor", type=float, default=0.35,
                    help="minimum mean greedy matching-prefix fraction "
                         "the int4 serve must reach vs the fp32 serve of "
                         "the same checkpoint (VERDICT r04 #6). The floor "
                         "separates 'lossy but sane' from 'broken': a "
                         "packing/dequant BUG craters agreement to ~0, "
                         "while legitimate small-group noise on this "
                         "worst-case model (64-wide contractions = "
                         "whole-axis scale groups) stays well above it")
    ap.add_argument("--kv-quantize", default="", choices=("", "int8"),
                    help="after the plain serving run passes, re-serve "
                         "the SAME checkpoint with the int8 KV cache and "
                         "re-run every memorized-agent assertion: greedy "
                         "faithfulness under KV quantization on learned "
                         "weights for one extra serving pass")
    ap.add_argument("--wide", action="store_true",
                    help="4x the model (d=128, f=256, 8 heads): the "
                         "capacity experiment for held-out phrasing "
                         "generalization (slower to train)")
    args = ap.parse_args()
    # Validate serve variants at parse time: a typo must not be found
    # AFTER the training run it would re-serve.
    args.serve_variants = ",".join(
        v.strip() for v in (args.serve_variants or "").split(",")
        if v.strip()
    )
    bad = [v for v in args.serve_variants.split(",")
           if v and v not in ("kv-int8", "int8", "int4")]
    if bad:
        ap.error(f"unknown --serve-variants entries: {', '.join(bad)} "
                 f"(expected kv-int8, int8, int4)")
    tasks = TASKS_MULTI if args.tasks == "multi" else TASKS_SINGLE

    import dataclasses

    from opsagent_tpu.models.config import get_config_preset
    from opsagent_tpu.models.loader import save_checkpoint
    from opsagent_tpu.parallel.mesh import make_mesh
    from opsagent_tpu.serving.tokenizer import ByteTokenizer, load_tokenizer
    from opsagent_tpu.training import (
        TrainConfig,
        init_train_state,
        make_train_step,
    )

    out = args.out or tempfile.mkdtemp(prefix="opsagent-tiny-agent-")
    os.makedirs(out, exist_ok=True)
    cfg = get_config_preset("tiny-test")
    if args.wide:
        cfg = dataclasses.replace(
            cfg, hidden_size=128, intermediate_size=256, num_heads=8,
            num_kv_heads=4,
        )
    if args.tokenizer == "bpe":
        try:
            import tokenizers  # noqa: F401 - probe the optional dep
            import transformers  # noqa: F401
        except ImportError as e:
            print(f"tokenizers/transformers unavailable ({e}); "
                  f"falling back to the byte tokenizer", file=sys.stderr)
            args.tokenizer = "byte"
    if args.tokenizer == "bpe":
        tok_path = train_bpe_tokenizer(out, tasks=tasks)
        tok = load_tokenizer(tok_path)
        # The lm head sizes to the trained vocab (specials included).
        cfg = dataclasses.replace(cfg, vocab_size=tok.vocab_size)
        print(f"bpe tokenizer: vocab {tok.vocab_size} at {tok_path}",
              file=sys.stderr)
    else:
        tok_path = ""
        tok = ByteTokenizer(vocab_size=cfg.vocab_size)
    rows = build_dataset(tok, tasks)
    S = 8 * ((max(len(ids) for ids, _ in rows) + 7) // 8)
    B = len(rows)
    tokens = np.full((B, S), tok.pad_id, np.int32)
    mask = np.zeros((B, S), np.float32)
    for i, (ids, m) in enumerate(rows):
        tokens[i, :len(ids)] = ids
        mask[i, :len(m)] = m
    print(f"dataset: {B} rows, padded to S={S}", file=sys.stderr)

    mesh = make_mesh(tp=1, dp=1, sp=1, devices=jax.devices()[:1])
    tc = TrainConfig(learning_rate=args.lr, weight_decay=0.0, remat=False)
    params, opt_state = init_train_state(
        cfg, tc, mesh, jax.random.PRNGKey(0), dtype=jnp.float32
    )
    step = make_train_step(cfg, tc, mesh, dtype=jnp.float32)
    tokens_j = jnp.asarray(tokens)
    mask_j = jnp.asarray(mask)

    t0 = time.perf_counter()
    loss = float("inf")
    for i in range(args.steps):
        params, opt_state, metrics = step(params, opt_state, tokens_j, mask_j)
        if i % 50 == 0 or i == args.steps - 1:
            loss = float(metrics["loss"])
            print(f"step {i:4d} loss {loss:.4f} "
                  f"({time.perf_counter()-t0:.0f}s)", file=sys.stderr)
            if loss < args.target_loss:
                break
    print(f"trained to loss {loss:.4f} in {time.perf_counter()-t0:.0f}s",
          file=sys.stderr)

    ckpt = os.path.join(out, "model.safetensors")
    save_checkpoint(ckpt, params)
    print(f"checkpoint saved: {ckpt}", file=sys.stderr)
    if args.skip_agent:
        return 0
    ok = run_agent(ckpt, tok_path, cfg, tasks, probe=not args.no_probe)
    # Re-serve the SAME checkpoint under each requested quantized
    # configuration and rerun the memorized assertions: greedy
    # faithfulness on LEARNED weights at one extra serving pass each
    # (training is the expensive part and happens once). int4's ANSWERS
    # are non-gating — tiny-test's 64-wide contraction axes collapse to
    # whole-axis scale groups, group-wise int4's worst case, so a
    # flipped answer is expected signal — but int4 DOES gate on greedy
    # prefix agreement vs the fp32 serve (--int4-floor; PERF.md "int4
    # fidelity policy"): a packing/dequant bug fails the run.
    variants = [v for v in (args.serve_variants or "").split(",") if v]
    if args.kv_quantize and "kv-int8" not in variants:
        variants.insert(0, "kv-int8")
    for v in variants:
        if not ok:
            break
        kvq = "int8" if v == "kv-int8" else ""
        wq = v if v in ("int8", "int4") else ""
        if not (kvq or wq):
            print(f"unknown serve variant {v!r}", file=sys.stderr)
            return 1
        print(f"re-serving with quantize={wq or '-'} "
              f"kv_quantize={kvq or '-'} [{v}]", file=sys.stderr)
        got = run_agent(ckpt, tok_path, cfg, tasks, probe=False,
                        kv_quantize=kvq, quantize=wq)
        if v == "int4":
            # int4's answer-level pass is NOT the gate at this scale
            # (tiny-test's 64-wide contractions collapse to whole-axis
            # scale groups — group-wise int4's worst case, so a flipped
            # answer is expected signal). The GATE is quantitative
            # greedy agreement vs the fp32 serve (VERDICT r04 #6): a
            # packing/dequant bug craters it to ~0, quantization noise
            # does not.
            agree = greedy_agreement(
                ckpt, tok_path, cfg, tasks, quantize="int4"
            )
            print(f"int4 variant {'PASSED' if got else 'DIVERGED'} "
                  f"(answers non-gating); greedy prefix agreement vs "
                  f"fp32 {agree:.3f} (gate floor {args.int4_floor})",
                  file=sys.stderr)
            if agree < args.int4_floor:
                print(f"int4 agreement {agree:.3f} < floor "
                      f"{args.int4_floor}: FAILED", file=sys.stderr)
                ok = False
        else:
            ok = got
    return 0 if ok else 1


def greedy_agreement(ckpt: str, tok_path: str, cfg, tasks,
                     quantize: str = "", kv_quantize: str = "",
                     max_tokens: int = 64) -> float:
    """Mean greedy matching-prefix fraction of a quantized serve vs the
    fp32 serve of the SAME checkpoint, over each task's turn-1 prompt
    (chat-templated by the serving path's own apply_chat_template).
    Prefix fraction, not positionwise match: greedy divergence compounds,
    so the first differing token ends the credited run — the strictest
    honest scalar for 'how far does the quantized model track fp32'."""
    from opsagent_tpu.serving.chat_template import apply_chat_template
    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    def gen(wq: str, kvq: str) -> list[list[int]]:
        eng = Engine(
            EngineConfig(
                model="tiny-test", checkpoint=ckpt, tokenizer=tok_path,
                dtype=jnp.float32, num_pages=256, page_size=16,
                max_pages_per_seq=64, max_batch_size=1,
                prefill_buckets=(128, 512, 1024),
                quantize=wq, kv_quantize=kvq,
            ),
            model_cfg=cfg,
        )
        outs = []
        for t in tasks:
            messages = [
                {"role": "system", "content": SYS_PROMPT},
                {"role": "user",
                 "content": f"Here are the instructions: "
                            f"{t['instruction']}"},
            ]
            ids = apply_chat_template(eng.tokenizer, messages)
            sid = eng.add_request(
                ids, SamplingParams(temperature=0.0, max_tokens=max_tokens)
            )
            while not eng.sequences[sid].done:
                eng.step([sid])
            outs.append(eng.finish(sid))
        return outs

    ref = gen("", "")
    got = gen(quantize, kv_quantize)
    fracs = []
    for a, b in zip(ref, got):
        n = 0
        for x, y in zip(a, b):
            if x != y:
                break
            n += 1
        fracs.append(n / max(1, len(a)))
    return sum(fracs) / max(1, len(fracs))


def run_agent(ckpt: str, tok_path: str, cfg, tasks=None,
              probe: bool = True, kv_quantize: str = "",
              quantize: str = "") -> bool:
    """Serve the trained checkpoint and run the real agent loop on EVERY
    task's instruction, asserting each memorized final answer."""
    from opsagent_tpu.agent.react import assistant_with_config
    from opsagent_tpu.serving import api as serving_api
    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.tools import ToolPrompt
    from opsagent_tpu.tools.replay import (
        MULTI_TASK_SCRIPT,
        NAMESPACES_SCRIPT,
        install_replay_kubectl,
    )

    tasks = tasks or TASKS_SINGLE
    install_replay_kubectl(
        MULTI_TASK_SCRIPT if len(tasks) > 1 else NAMESPACES_SCRIPT
    )

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
            quantize=quantize,
            kv_quantize=kv_quantize,
        ),
        model_cfg=cfg,
    )
    stack = serving_api.ServingStack(engine)
    serving_api.install_stack("tiny-agent", stack)
    def run_one(phrasing: str, t, tag: str = "") -> bool:
        label = f"{phrasing}{tag}"
        messages = [
            {"role": "system", "content": SYS_PROMPT},
            {"role": "user",
             "content": f"Here are the instructions: {phrasing}"},
        ]
        try:
            answer, history = assistant_with_config(
                "tpu://tiny-agent", messages, 256, False, True, 4, "", ""
            )
        except Exception as e:  # noqa: BLE001 - a mis-routed probe can
            # loop until the page budget rejects its grown history; that
            # is a FAILED probe, not a crashed demo. GATING runs re-raise:
            # an engine fault there needs its traceback, not a one-liner.
            if not tag:
                raise
            print(f"[{label}] agent error: {e} FAILED")
            return False
        print(f"--- transcript [{label}] ---", file=sys.stderr)
        for m in history:
            print(f"[{m['role']}] {str(m['content'])[:300]}",
                  file=sys.stderr)
        try:
            final = ToolPrompt.from_json(answer).final_answer
        except ValueError:
            final = ""
        ok = final == t["final"]
        verdict = "PASSED" if ok else f"FAILED (want {t['final']!r})"
        print(f"[{label}] final answer: {final!r} {verdict}")
        return ok

    try:
        all_ok = True
        held_total = held_ok = 0
        for t in tasks:
            for phrasing in train_phrasings(t):
                all_ok = run_one(phrasing, t) and all_ok
            held = heldout_phrasing(t)
            if probe and held is not None:
                # Robustness probe, reported but NOT gating: a tiny
                # 2-layer model is not owed paraphrase generalization.
                held_total += 1
                if run_one(held, t, tag=" (HELD-OUT)"):
                    held_ok += 1
        print(f"agent {'PASSED' if all_ok else 'FAILED'} "
              f"({len(tasks)} tasks)")
        if held_total:
            print(f"held-out phrasings: {held_ok}/{held_total} correct "
                  f"(robustness probe, non-gating)")
        return all_ok
    finally:
        stack.close()
        serving_api.uninstall_stack("tiny-agent")


if __name__ == "__main__":
    sys.exit(main())
