"""HF ``config.json`` <-> ModelConfig derivation (models/config.py).

``config_from_hf`` makes any HF llama/qwen2 checkpoint DIRECTORY servable
without a hand-written preset — the engine reads the architecture from
the checkpoint's own metadata, the way the reference reads nothing at all
(its model is a remote API, reference pkg/llms/openai.go:69). The slow
test drives scripts/run_real_checkpoint.py end to end on a synthesized
HF-format directory: config.json + model.safetensors + fast-tokenizer
files, exactly the layout of a real Llama/Qwen release.
"""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_hf_config_roundtrip_llama():
    from opsagent_tpu.models.config import (
        RopeScalingConfig,
        config_from_hf,
        get_config_preset,
        hf_config_dict,
    )

    base = get_config_preset("tiny-test")
    cfg = dataclasses.replace(
        base,
        rope_scaling=RopeScalingConfig(
            rope_type="llama3", factor=8.0, original_max_position=8192,
            low_freq_factor=1.0, high_freq_factor=4.0,
        ),
    )
    hf = hf_config_dict(cfg)
    assert hf["model_type"] == "llama"
    # Write to a dir and re-derive.
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        with open(os.path.join(d, "config.json"), "w") as f:
            json.dump(hf, f)
        back = config_from_hf(d, name=cfg.name)
    for fld in ("vocab_size", "hidden_size", "intermediate_size",
                "num_layers", "num_heads", "num_kv_heads", "rope_theta",
                "rms_norm_eps", "attn_bias", "tie_embeddings",
                "max_position", "rope_scaling"):
        assert getattr(back, fld) == getattr(cfg, fld), fld


def test_hf_config_qwen2_and_yarn(tmp_path):
    from opsagent_tpu.models.config import config_from_hf

    hf = {
        "model_type": "qwen2",
        "vocab_size": 1000,
        "hidden_size": 64,
        "intermediate_size": 128,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6,
        "tie_word_embeddings": True,
        "max_position_embeddings": 32768,
        "rope_scaling": {
            "type": "yarn", "factor": 4.0,
            "original_max_position_embeddings": 4096,
            "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
        },
    }
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf, f)
    cfg = config_from_hf(str(tmp_path))
    assert cfg.attn_bias  # qwen2 => qkv biases
    assert cfg.tie_embeddings
    assert cfg.rope_scaling.rope_type == "yarn"
    assert cfg.rope_scaling.factor == 4.0
    assert cfg.max_position == 32768


def test_hf_config_rejects_unknown_family(tmp_path):
    from opsagent_tpu.models.config import config_from_hf

    with open(tmp_path / "config.json", "w") as f:
        json.dump({"model_type": "mixtral"}, f)
    with pytest.raises(ValueError, match="mixtral"):
        config_from_hf(str(tmp_path))


def test_hf_config_deepseek_v2_matches_preset(tmp_path):
    """A V2-Lite-shaped config.json derives the SAME ModelConfig the
    hand-written preset carries (which mirrors the HF fields 1:1) — MLA,
    MoE, and YaRN scaling included."""
    from opsagent_tpu.models.config import config_from_hf, get_config_preset

    hf = {
        "model_type": "deepseek_v2",
        "vocab_size": 102400,
        "hidden_size": 2048,
        "intermediate_size": 10944,
        "moe_intermediate_size": 1408,
        "num_hidden_layers": 27,
        "num_attention_heads": 16,
        "num_key_value_heads": 16,
        "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6,
        "max_position_embeddings": 163840,
        "n_routed_experts": 64,
        "num_experts_per_tok": 6,
        "n_shared_experts": 2,
        "first_k_dense_replace": 1,
        "moe_layer_freq": 1,
        "norm_topk_prob": False,
        "scoring_func": "softmax",
        "q_lora_rank": None,
        "kv_lora_rank": 512,
        "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64,
        "v_head_dim": 128,
        "rope_scaling": {
            "type": "yarn", "factor": 40.0,
            "original_max_position_embeddings": 4096,
            "beta_fast": 32, "beta_slow": 1,
            "mscale": 0.707, "mscale_all_dim": 0.707,
        },
    }
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf, f)
    cfg = config_from_hf(str(tmp_path))
    want = get_config_preset("deepseek-v2-lite")
    for fld in ("vocab_size", "hidden_size", "intermediate_size",
                "num_layers", "num_heads", "num_kv_heads", "head_dim_",
                "rope_theta", "rms_norm_eps", "max_position",
                "moe_layer_start", "moe", "mla", "rope_scaling"):
        assert getattr(cfg, fld) == getattr(want, fld), fld


def test_hf_config_deepseek_v3_router_fields(tmp_path):
    from opsagent_tpu.models.config import config_from_hf

    hf = {
        "model_type": "deepseek_v3",
        "vocab_size": 129280, "hidden_size": 7168,
        "intermediate_size": 18432, "moe_intermediate_size": 2048,
        "num_hidden_layers": 61, "num_attention_heads": 128,
        "rms_norm_eps": 1e-6, "max_position_embeddings": 163840,
        "n_routed_experts": 256, "num_experts_per_tok": 8,
        "n_shared_experts": 1, "first_k_dense_replace": 3,
        "norm_topk_prob": True, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "n_group": 8, "topk_group": 4,
        "q_lora_rank": 1536, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128,
    }
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf, f)
    cfg = config_from_hf(str(tmp_path))
    assert cfg.moe.scoring_func == "sigmoid"
    assert cfg.moe.norm_topk_prob and cfg.moe.routed_scaling_factor == 2.5
    assert (cfg.moe.n_group, cfg.moe.topk_group) == (8, 4)
    assert cfg.mla.q_lora_rank == 1536 and cfg.mla.latent_cache
    assert cfg.num_kv_heads == 128  # MLA: no GQA
    assert cfg.moe_layer_start == 3
    assert cfg.head_dim_ == 192


@pytest.mark.slow
def test_run_real_checkpoint_script_auto_config(tmp_path):
    """scripts/run_real_checkpoint.py with --model-name auto on a
    synthesized HF-layout dir (config.json drives the architecture): the
    full loader -> engine -> agent-loop -> kubectl-replay path the real
    8B run takes, hermetic on CPU with random weights (the ToolPrompt
    FSM guarantees schema-valid JSON regardless of weights)."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from train_tiny_agent import train_bpe_tokenizer

    from opsagent_tpu.models import llama
    from opsagent_tpu.models.config import (
        config_from_hf,
        get_config_preset,
        hf_config_dict,
    )
    from opsagent_tpu.models.loader import save_checkpoint
    from opsagent_tpu.serving.tokenizer import load_tokenizer

    from opsagent_tpu.agent.prompts import REACT_SYSTEM_PROMPT

    ckpt_dir = tmp_path / "tiny-hf-release"
    ckpt_dir.mkdir()
    # Include the real system prompt in the tokenizer corpus so the
    # agent-loop prompt stays a few hundred tokens, not ~12k near-bytes.
    tok_dir = train_bpe_tokenizer(
        str(ckpt_dir), extra_corpus=(REACT_SYSTEM_PROMPT,), vocab_size=2048
    )
    # Real HF releases keep tokenizer files at the dir root.
    for fn in os.listdir(tok_dir):
        shutil.move(os.path.join(tok_dir, fn), ckpt_dir / fn)
    os.rmdir(tok_dir)
    tok = load_tokenizer(str(ckpt_dir))

    cfg = dataclasses.replace(
        get_config_preset("tiny-test"), vocab_size=tok.vocab_size
    )
    with open(ckpt_dir / "config.json", "w") as f:
        json.dump(hf_config_dict(cfg), f)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    save_checkpoint(str(ckpt_dir / "model.safetensors"), params)

    # Sanity: the auto-derived config matches what the weights were built
    # from (name comes from the dir).
    derived = config_from_hf(str(ckpt_dir))
    assert derived.vocab_size == cfg.vocab_size
    assert derived.name == "tiny-hf-release"

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    out = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "scripts", "run_real_checkpoint.py"),
            "--checkpoint", str(ckpt_dir),
            "--model-name", "auto",
            "--max-iterations", "2",
            # The toy BPE tokenizer (trained on the 2-conv corpus only)
            # spends ~12k tokens on the ReAct system prompt; give the KV
            # pool room for it.
            "--num-pages", "2048",
            "--max-pages-per-seq", "1024",
            "--transcript", str(tmp_path / "transcript.md"),
        ],
        capture_output=True, text=True, timeout=3000, env=env, cwd=REPO,
    )
    assert out.returncode == 0, (out.stdout + out.stderr)[-3000:]
    last = out.stdout.strip().splitlines()[-1]
    assert json.loads(last)["ok"] is True
    assert "config.json -> tiny-hf-release" in out.stderr
    assert (tmp_path / "transcript.md").exists()


def test_hf_config_rejects_unknown_scoring_func(tmp_path):
    from opsagent_tpu.models.config import config_from_hf

    with open(tmp_path / "config.json", "w") as f:
        json.dump({
            "model_type": "deepseek_v3", "vocab_size": 100,
            "hidden_size": 64, "intermediate_size": 128,
            "num_hidden_layers": 2, "num_attention_heads": 4,
            "n_routed_experts": 8, "num_experts_per_tok": 2,
            "kv_lora_rank": 16, "qk_nope_head_dim": 8,
            "qk_rope_head_dim": 8, "v_head_dim": 8,
            "scoring_func": "mystery",
        }, f)
    with pytest.raises(ValueError, match="mystery"):
        config_from_hf(str(tmp_path))


def test_resolve_model_policy(tmp_path):
    """One shared resolution policy (serve-engine + run_real_checkpoint):
    presets pass through; auto derives from config.json and the
    checkpoint's own metadata is authoritative even when the dir's
    basename collides with a preset name."""
    from opsagent_tpu.models.config import (
        get_config_preset,
        hf_config_dict,
        resolve_model,
    )

    assert resolve_model("tiny-test") == ("tiny-test", None)
    with pytest.raises(ValueError, match="requires --checkpoint"):
        resolve_model("auto")

    # A dir NAMED like a preset but carrying different dims: the derived
    # config must win (a renamed snapshot / fine-tune with other dims).
    ckpt = tmp_path / "tiny-test"
    ckpt.mkdir()
    cfg = dataclasses.replace(
        get_config_preset("tiny-test"), vocab_size=777, hidden_size=96,
        intermediate_size=192, num_heads=6, num_kv_heads=3, head_dim=0,
    )
    with open(ckpt / "config.json", "w") as f:
        json.dump(hf_config_dict(cfg), f)
    name, derived = resolve_model("auto", str(ckpt))
    assert name == "tiny-test"
    assert derived is not None and derived.vocab_size == 777
    assert derived.hidden_size == 96


def test_restart_factory_keeps_auto_model_cfg():
    """ADVICE-style regression: the slice-restart factory must carry the
    resolved model_cfg — an auto-derived (non-preset) architecture has no
    preset to fall back to, so a recovery rebuild without it would die in
    get_config_preset on the checkpoint-dir name."""
    import dataclasses as dc

    import jax.numpy as jnp

    from opsagent_tpu.models.config import get_config_preset
    from opsagent_tpu.serving.api import ServingStack
    from opsagent_tpu.serving.engine import Engine, EngineConfig

    cfg = dc.replace(get_config_preset("tiny-test"), name="no-such-preset")
    eng = Engine(
        EngineConfig(
            model="no-such-preset", dtype=jnp.float32, tp=1,
            num_pages=16, page_size=8, max_pages_per_seq=4,
            max_batch_size=2, prefill_buckets=(16,),
        ),
        model_cfg=cfg,
    )
    stack = ServingStack(eng)
    try:
        rebuilt = stack.scheduler._engine_factory()
        assert rebuilt.model_cfg.name == "no-such-preset"
    finally:
        stack.close()


def test_hf_config_dict_roundtrips_moe_mla():
    """Export side: a V3-shaped (MLA + sigmoid MoE) and a MoE-only config
    roundtrip through hf_config_dict -> config_from_hf. The only allowed
    delta is mla.latent_cache: derivation always serves V2/V3 with the
    compressed latent pages."""
    from opsagent_tpu.models.config import (
        MLAConfig,
        MoEConfig,
        config_from_hf,
        get_config_preset,
        hf_config_dict,
    )

    v3ish = dataclasses.replace(
        get_config_preset("tiny-mla"),
        num_layers=3,
        moe=MoEConfig(
            num_experts=4, num_experts_per_token=2, num_shared_experts=1,
            expert_intermediate_size=32, norm_topk_prob=True,
            routed_scaling_factor=2.5, scoring_func="sigmoid",
            n_group=2, topk_group=1,
        ),
        moe_layer_start=1,
    )
    moe_only = get_config_preset("tiny-moe")

    import tempfile

    for cfg, want_mt in ((v3ish, "deepseek_v3"), (moe_only, "deepseek")):
        hf = hf_config_dict(cfg)
        assert hf["model_type"] == want_mt
        with tempfile.TemporaryDirectory() as d:
            with open(os.path.join(d, "config.json"), "w") as f:
                json.dump(hf, f)
            back = config_from_hf(d, name=cfg.name)
        assert back.moe == cfg.moe
        if cfg.mla:
            assert back.mla == dataclasses.replace(
                cfg.mla, latent_cache=True
            )
            assert back.num_kv_heads == cfg.num_heads
        for fld in ("vocab_size", "hidden_size", "intermediate_size",
                    "num_layers", "num_heads", "moe_layer_start",
                    "max_position"):
            assert getattr(back, fld) == getattr(cfg, fld), fld


@pytest.mark.slow
def test_run_real_checkpoint_script_deepseek_auto(tmp_path):
    """The auto path on a synthesized DeepSeek-V3-SHAPED release dir:
    config.json (MLA + sigmoid MoE) -> config_from_hf -> loader (HF
    deepseek weight names incl. router e_score_correction_bias) ->
    latent-cache engine -> FSM-constrained agent loop. The same flow a
    real V2-Lite/V3 download takes, at toy scale with random weights.

    The heaviest test in the suite (~10 min solo: full production warmup
    of an MLA MoE engine on CPU). It passes solo reliably but can starve
    past its subprocess timeout when run under a fully loaded
    ``pytest -n`` box — run it in the slow lane / a lightly loaded
    worker, not sandwiched into a saturated parallel session."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        from train_tiny_agent import train_bpe_tokenizer
    finally:
        sys.path.remove(os.path.join(REPO, "scripts"))

    from opsagent_tpu.agent.prompts import REACT_SYSTEM_PROMPT
    from opsagent_tpu.models import llama
    from opsagent_tpu.models.config import (
        MoEConfig,
        config_from_hf,
        get_config_preset,
        hf_config_dict,
    )
    from opsagent_tpu.models.loader import save_checkpoint
    from opsagent_tpu.serving.tokenizer import load_tokenizer

    ckpt_dir = tmp_path / "tiny-v3-release"
    ckpt_dir.mkdir()
    tok_dir = train_bpe_tokenizer(
        str(ckpt_dir), extra_corpus=(REACT_SYSTEM_PROMPT,), vocab_size=2048
    )
    for fn in os.listdir(tok_dir):
        shutil.move(os.path.join(tok_dir, fn), ckpt_dir / fn)
    os.rmdir(tok_dir)
    tok = load_tokenizer(str(ckpt_dir))

    cfg = dataclasses.replace(
        get_config_preset("tiny-mla"),
        vocab_size=tok.vocab_size,
        num_layers=3,
        max_position=16384,
        moe=MoEConfig(
            num_experts=4, num_experts_per_token=2, num_shared_experts=1,
            expert_intermediate_size=32, norm_topk_prob=True,
            routed_scaling_factor=2.5, scoring_func="sigmoid",
            n_group=2, topk_group=1,
        ),
        moe_layer_start=1,
    )
    with open(ckpt_dir / "config.json", "w") as f:
        json.dump(hf_config_dict(cfg), f)
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    params["moe_layers"]["router_bias"] = jnp.asarray(
        np.linspace(-1, 1, 2 * 4).reshape(2, 4), jnp.float32
    )
    save_checkpoint(str(ckpt_dir / "model.safetensors"), params, cfg=cfg)

    derived = config_from_hf(str(ckpt_dir))
    assert derived.mla is not None and derived.mla.latent_cache
    assert derived.moe is not None

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    out = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "scripts", "run_real_checkpoint.py"),
            "--checkpoint", str(ckpt_dir),
            "--model-name", "auto",
            "--max-iterations", "1",
            "--num-pages", "2048",
            "--max-pages-per-seq", "1024",
            "--transcript", str(tmp_path / "transcript.md"),
        ],
        capture_output=True, text=True, timeout=3000, env=env, cwd=REPO,
    )
    assert out.returncode == 0, (out.stdout + out.stderr)[-3000:]
    last = out.stdout.strip().splitlines()[-1]
    assert json.loads(last)["ok"] is True
    assert "config.json -> tiny-v3-release" in out.stderr


def test_hf_config_dict_preserves_attn_bias_on_moe():
    """A Qwen2-MoE-style config (moe set, attn_bias=True) exports as the
    deepseek family but must keep attention_bias, or the re-imported
    model would silently drop the q/k/v bias params."""
    from opsagent_tpu.models.config import (
        config_from_hf,
        get_config_preset,
        hf_config_dict,
    )

    cfg = dataclasses.replace(get_config_preset("tiny-moe"), attn_bias=True)
    hf = hf_config_dict(cfg)
    assert hf["model_type"] == "deepseek" and hf["attention_bias"] is True
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        with open(os.path.join(d, "config.json"), "w") as f:
            json.dump(hf, f)
        back = config_from_hf(d, name=cfg.name)
    assert back.attn_bias is True


def test_hf_config_mistral_family(tmp_path):
    """Mistral releases are llama-shaped (same weight names, GQA, silu)
    once sliding-window attention is off: v0.3/Nemo-class configs
    (sliding_window: null, explicit head_dim, rope_theta 1e6) must
    derive; a v0.1-class ACTIVE window must be rejected loudly rather
    than served with wrong (full) attention."""
    from opsagent_tpu.models.config import config_from_hf

    hf = {
        "model_type": "mistral",
        "architectures": ["MistralForCausalLM"],
        "vocab_size": 32768,
        "hidden_size": 64,
        "intermediate_size": 128,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "head_dim": 32,          # Nemo-style: explicit, != hidden/heads
        "rope_theta": 1000000.0,
        "rms_norm_eps": 1e-5,
        "sliding_window": None,  # v0.3-class: window disabled
        "max_position_embeddings": 32768,
    }
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf, f)
    cfg = config_from_hf(str(tmp_path))
    assert not cfg.attn_bias          # mistral has no qkv biases
    assert cfg.num_kv_heads == 2      # GQA preserved
    assert cfg.head_dim == 32         # explicit head_dim honored
    assert cfg.rope_theta == 1000000.0

    # A window >= the position window is equivalent to disabled.
    hf["sliding_window"] = 32768
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf, f)
    assert config_from_hf(str(tmp_path)).num_layers == 2

    # v0.1-class active window: reject, never silently full-attend.
    hf["sliding_window"] = 4096
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf, f)
    with pytest.raises(ValueError, match="sliding-window"):
        config_from_hf(str(tmp_path))


def test_hf_config_qwen2_sliding_window_gate(tmp_path):
    """Qwen2 carries sliding_window fields gated by use_sliding_window:
    false (every shipped Qwen2.5 release) must derive; true with an
    active window must be rejected like mistral."""
    from opsagent_tpu.models.config import config_from_hf

    hf = {
        "model_type": "qwen2",
        "vocab_size": 1000,
        "hidden_size": 64,
        "intermediate_size": 128,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "max_position_embeddings": 32768,
        "sliding_window": 4096,
        "use_sliding_window": False,
    }
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf, f)
    assert config_from_hf(str(tmp_path)).num_layers == 2

    hf["use_sliding_window"] = True
    with open(tmp_path / "config.json", "w") as f:
        json.dump(hf, f)
    with pytest.raises(ValueError, match="sliding-window"):
        config_from_hf(str(tmp_path))


def test_hf_config_qwen3_family():
    """Qwen3 derives with qk_norm on, no attn biases, and the explicit
    head_dim honored — against the REAL fixture config.json transformers
    wrote (tests/fixtures/tiny-qwen3-hf), not a hand-mocked dict."""
    from opsagent_tpu.models.config import config_from_hf

    path = os.path.join(REPO, "tests", "fixtures", "tiny-qwen3-hf")
    if not os.path.isdir(path):
        pytest.skip("qwen3 fixture not generated")
    cfg = config_from_hf(path)
    assert cfg.qk_norm
    assert not cfg.attn_bias
    assert cfg.head_dim == 32 and cfg.head_dim_ == 32
    assert cfg.num_kv_heads == 2


def test_hf_config_qwen3_moe_family(tmp_path):
    """Qwen3-MoE derives from the real fixture config (qk_norm + softmax
    top-k MoE, every layer sparse), roundtrips through hf_config_dict's
    qwen3_moe export, and rejects the interleaved-dense layouts the
    stacked tree cannot express."""
    import dataclasses
    import shutil

    from opsagent_tpu.models.config import config_from_hf, hf_config_dict

    src = os.path.join(REPO, "tests", "fixtures", "tiny-qwen3-moe-hf")
    if not os.path.isdir(src):
        pytest.skip("qwen3-moe fixture not generated")
    cfg = config_from_hf(src)
    assert cfg.qk_norm and cfg.moe is not None
    assert cfg.moe.scoring_func == "softmax"
    assert cfg.moe.num_shared_experts == 0
    assert cfg.moe.norm_topk_prob
    assert cfg.moe_layer_start == 0

    out = hf_config_dict(cfg)
    assert out["model_type"] == "qwen3_moe"
    with open(tmp_path / "config.json", "w") as f:
        json.dump(out, f)
    back = config_from_hf(str(tmp_path), name=cfg.name)
    assert dataclasses.asdict(back) == dataclasses.asdict(cfg)

    # Interleaved dense layers: reject, the stacked tree is contiguous.
    with open(os.path.join(src, "config.json")) as f:
        hf = json.load(f)
    hf["mlp_only_layers"] = [1]
    bad = tmp_path / "interleaved"
    bad.mkdir()
    with open(bad / "config.json", "w") as f:
        json.dump(hf, f)
    with pytest.raises(ValueError, match="mlp_only_layers"):
        config_from_hf(str(bad))
