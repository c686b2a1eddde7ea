"""Multi-head Latent Attention (DeepSeek-V2/V3 family, models/llama.py
MLA paths): decode/prefill consistency against the all-positions oracle,
HF-name checkpoint roundtrip, tensor parallelism, and serving.

MLA serves in two layouts (config.MLAConfig): uncompressed per-head k/v
(v zero-padded to the qk head dim so the shared paged-cache machinery is
untouched) and the compressed latent cache with weight-absorbed decode;
both are oracle-tested here.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opsagent_tpu.models import llama
from opsagent_tpu.models.config import get_config_preset

CFG = get_config_preset("tiny-mla")
DTYPE = jnp.float32


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(0), dtype=DTYPE)


def test_forward_shapes_and_finite(params):
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (2, 8), 0, CFG.vocab_size
    )
    logits = llama.forward_full(params, CFG, tokens, dtype=DTYPE)
    assert logits.shape == (2, 8, CFG.vocab_size)
    assert bool(jnp.isfinite(logits).all())


def test_decode_chain_matches_forward_full(params):
    """Prefill, then teacher-force decode steps; every step's logits must
    match the all-at-once causal forward — proving the roped shared-key /
    padded-v cache layout reproduces MLA attention exactly."""
    S_total, S_prompt = 10, 4
    tokens = jax.random.randint(
        jax.random.PRNGKey(6), (1, S_total), 0, CFG.vocab_size
    )
    full = llama.forward_full(params, CFG, tokens, dtype=DTYPE)

    cache = llama.make_cache(CFG, num_pages=8, page_size=4, dtype=DTYPE)
    table = jnp.array([[2, 5, 7]], jnp.int32)
    logits, cache = llama.prefill(
        params, CFG, tokens[:, :S_prompt], jnp.array([S_prompt]),
        cache, table, dtype=DTYPE,
    )
    np.testing.assert_allclose(
        logits[0], full[0, S_prompt - 1], rtol=2e-4, atol=2e-4
    )
    for t in range(S_prompt, S_total):
        logits, cache = llama.decode_step(
            params, CFG, tokens[:, t], jnp.array([t]), cache, table,
            active=jnp.array([True]), dtype=DTYPE,
        )
        np.testing.assert_allclose(
            logits[0], full[0, t], rtol=3e-4, atol=3e-4,
            err_msg=f"decode step at position {t}",
        )


def test_checkpoint_roundtrip(tmp_path, params):
    """save_checkpoint (HF deepseek naming: kv_a_proj_with_mqa recombined,
    o_proj unpadded) -> load_checkpoint -> identical logits."""
    from opsagent_tpu.models.loader import load_checkpoint, save_checkpoint

    ckpt = tmp_path / "model.safetensors"
    save_checkpoint(str(ckpt), params, cfg=CFG)
    loaded = load_checkpoint(str(ckpt), CFG, dtype=DTYPE)
    tokens = jnp.array([[1, 2, 3, 4, 5]], jnp.int32)
    l1 = llama.forward_full(params, CFG, tokens, dtype=DTYPE)
    l2 = llama.forward_full(loaded, CFG, tokens, dtype=DTYPE)
    np.testing.assert_allclose(
        np.asarray(l1), np.asarray(l2), rtol=1e-5, atol=1e-5
    )


def test_tp_sharded_prefill_matches_single_device(params):
    """tp=4 (heads shard 4 ways; wuq/wukv column-parallel, wo
    row-parallel) must be numerically equivalent to unsharded."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from opsagent_tpu.parallel.mesh import make_mesh, shard_params

    tokens = jax.random.randint(
        jax.random.PRNGKey(3), (2, 8), 0, CFG.vocab_size
    )
    ref = llama.forward_full(params, CFG, tokens, dtype=DTYPE)

    mesh = make_mesh(tp=4, dp=2, sp=1)
    sharded = shard_params(params, llama.param_specs(CFG), mesh)
    with mesh:
        out = jax.jit(
            lambda p, t: llama.forward_full(p, CFG, t, dtype=DTYPE),
            in_shardings=(None, NamedSharding(mesh, P("dp"))),
        )(sharded, tokens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


def test_engine_serves_mla(tmp_path):
    """The serving engine generates from an MLA model (attention backend
    forced to the shape-agnostic xla gather) and greedy generation is
    deterministic across engines."""
    from opsagent_tpu.serving.engine import Engine, EngineConfig

    outs = []
    for _ in range(2):
        eng = Engine(EngineConfig(
            model="tiny-mla",
            dtype=DTYPE,
            num_pages=64,
            page_size=8,
            max_pages_per_seq=16,
            max_batch_size=2,
            prefill_buckets=(16,),
        ))
        assert eng.kernels.attn == "xla"
        outs.append(eng.generate([[1, 2, 3, 4], [9, 8, 7]], None))
    assert outs[0] == outs[1]
    assert all(len(t) >= 1 for t in outs[0])


def test_deepseek_presets_validate():
    """The real DeepSeek configs construct valid parameter trees (checked
    abstractly — no 671B allocation) with the MLA geometry invariants."""
    for name in ("deepseek-v2-lite", "deepseek-v3"):
        cfg = get_config_preset(name)
        assert cfg.mla is not None
        assert cfg.head_dim_ == cfg.mla.qk_head_dim
        shapes = jax.eval_shape(
            lambda c=cfg: llama.init_params(
                c, jax.random.PRNGKey(0), dtype=jnp.bfloat16
            )
        )
        specs = llama.param_specs(cfg)
        # Every param leaf has a matching spec leaf.
        assert jax.tree.structure(
            shapes, is_leaf=lambda x: hasattr(x, "shape")
        ).num_leaves == jax.tree.structure(specs).num_leaves


def test_mla_geometry_validation():
    bad = dataclasses.replace(CFG, head_dim=32)
    with pytest.raises(ValueError, match="qk_head_dim"):
        llama.init_params(bad, jax.random.PRNGKey(0), dtype=DTYPE)


def test_rope_convention_matches_hf_interleaved():
    """Loading permutes DeepSeek's INTERLEAVED rope columns to half-split;
    attention scores through our (permuted weights + half-split rope)
    path must equal the HF convention (interleaved weights, activations
    de-interleaved before rotate_half). Scores are the invariant —
    per-dim layout cancels when q and k are permuted consistently."""
    from opsagent_tpu.models.loader import _rope_interleave_to_halfsplit
    from opsagent_tpu.ops.rope import apply_rope, rope_table

    rng = np.random.default_rng(0)
    d, dr, S = 12, 8, 5
    x = rng.standard_normal((1, S, d)).astype(np.float32)
    w = rng.standard_normal((d, dr)).astype(np.float32)  # HF layout
    positions = jnp.arange(S)[None, :]
    cos, sin = rope_table(positions, dr, 10000.0)

    # HF convention: project with raw weights, de-interleave activations,
    # then standard half-split rotate (what rotate_half + their transpose
    # trick computes).
    perm = _rope_interleave_to_halfsplit(dr)
    hf_act = (x @ w)[..., perm]            # de-interleave == perm gather
    hf_roped = apply_rope(
        jnp.asarray(hf_act)[:, :, None, :], cos, sin
    )[:, :, 0]

    # Our convention: permute WEIGHT columns at load, then half-split rope.
    ours_act = x @ w[:, perm]
    ours_roped = apply_rope(
        jnp.asarray(ours_act)[:, :, None, :], cos, sin
    )[:, :, 0]

    np.testing.assert_allclose(
        np.asarray(hf_roped), np.asarray(ours_roped), rtol=1e-6, atol=1e-6
    )


def test_engine_rejects_prompt_beyond_context_window():
    from opsagent_tpu.serving.engine import Engine, EngineConfig, InvalidRequest

    eng = Engine(EngineConfig(
        model="tiny-mla", dtype=DTYPE, num_pages=64, page_size=8,
        max_pages_per_seq=400, max_batch_size=1, prefill_buckets=(16,),
    ))
    too_long = list(range(1, CFG.max_position + 2))
    with pytest.raises(InvalidRequest, match="context window"):
        eng.begin_request([t % 500 for t in too_long])


def test_generation_budget_clamped_to_context_window():
    """Admission clamps max_tokens so decode never runs rope positions
    past the model window; the request finishes with reason 'length'."""
    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    pages_needed = (CFG.max_position // 8) + 4
    eng = Engine(EngineConfig(
        model="tiny-mla", dtype=DTYPE, num_pages=pages_needed + 8,
        page_size=8, max_pages_per_seq=pages_needed, max_batch_size=1,
        prefill_buckets=(2048,),
    ))
    n = CFG.max_position - 3
    sid = eng.add_request(
        [1 + (i % 400) for i in range(n)],
        SamplingParams(temperature=0.0, max_tokens=500),
    )
    assert eng.sequences[sid].params.max_tokens == 3


LATENT_CFG = dataclasses.replace(
    CFG, mla=dataclasses.replace(CFG.mla, latent_cache=True)
)


def test_latent_cache_decode_chain_matches_oracle(params):
    """The weight-absorbed latent cache (MQA over [c_kv, k_rope] latents)
    must reproduce the materialized attention exactly: prefill + decode
    chain against the forward_full oracle, same weights."""
    S_total, S_prompt = 10, 4
    tokens = jax.random.randint(
        jax.random.PRNGKey(6), (1, S_total), 0, CFG.vocab_size
    )
    full = llama.forward_full(params, LATENT_CFG, tokens, dtype=DTYPE)

    cache = llama.make_cache(LATENT_CFG, num_pages=8, page_size=4, dtype=DTYPE)
    # one merged row a token, [L, N, P, page_dim]: no unit kv-head axis,
    # the 40-wide latent padded to the 128 lanes (as held before, the
    # chip's compiler copied the whole cache a step)
    assert (LATENT_CFG.mla.latent_dim, LATENT_CFG.mla.page_dim) == (40, 128)
    assert cache["k"].shape == (LATENT_CFG.num_layers, 8, 4, 128)
    table = jnp.array([[2, 5, 7]], jnp.int32)
    logits, cache = llama.prefill(
        params, LATENT_CFG, tokens[:, :S_prompt], jnp.array([S_prompt]),
        cache, table, dtype=DTYPE,
    )
    np.testing.assert_allclose(
        logits[0], full[0, S_prompt - 1], rtol=2e-4, atol=2e-4
    )
    for t in range(S_prompt, S_total):
        logits, cache = llama.decode_step(
            params, LATENT_CFG, tokens[:, t], jnp.array([t]), cache, table,
            active=jnp.array([True]), dtype=DTYPE,
        )
        np.testing.assert_allclose(
            logits[0], full[0, t], rtol=3e-4, atol=3e-4,
            err_msg=f"latent decode step at position {t}",
        )


def test_latent_cache_prefix_admission_matches_oracle(params):
    """prefill_with_prefix over latent pages (tail attends the absorbed
    form against cached latents) equals the oracle."""
    tokens = jax.random.randint(
        jax.random.PRNGKey(8), (1, 12), 0, CFG.vocab_size
    )
    full = llama.forward_full(params, LATENT_CFG, tokens, dtype=DTYPE)
    cache = llama.make_cache(LATENT_CFG, num_pages=8, page_size=4, dtype=DTYPE)
    table = jnp.array([[0, 3, 6]], jnp.int32)
    # Prefill the first 8, then admit the 4-token tail against the prefix.
    _, cache = llama.prefill(
        params, LATENT_CFG, tokens[:, :8], jnp.array([8]),
        cache, table, dtype=DTYPE,
    )
    logits, cache = llama.prefill_with_prefix(
        params, LATENT_CFG, tokens[:, 8:], jnp.array([8]), jnp.array([4]),
        cache, table, dtype=DTYPE,
    )
    np.testing.assert_allclose(
        np.asarray(logits[0]), np.asarray(full[0, 11]), rtol=3e-4, atol=3e-4
    )


def test_latent_engine_matches_materialized_engine():
    """End to end: the serving engine with latent_cache generates the
    SAME greedy tokens as the uncompressed-cache engine."""
    from opsagent_tpu.serving.engine import Engine, EngineConfig

    outs = []
    for model_cfg in (CFG, LATENT_CFG):
        eng = Engine(
            EngineConfig(
                model="tiny-mla",
                dtype=DTYPE,
                num_pages=64,
                page_size=8,
                max_pages_per_seq=16,
                max_batch_size=2,
                prefill_buckets=(16,),
            ),
            model_cfg=model_cfg,
        )
        outs.append(eng.generate([[1, 2, 3, 4], [9, 8, 7]], None))
    assert outs[0] == outs[1]


def test_latent_engine_int8_quantized():
    """Weight-only int8 under the latent cache: the absorbed path must
    dequantize wukv before its per-head reshape (regression: QuantizedLinear
    has no reshape)."""
    from opsagent_tpu.serving.engine import Engine, EngineConfig

    eng = Engine(
        EngineConfig(
            model="tiny-mla",
            dtype=DTYPE,
            num_pages=64,
            page_size=8,
            max_pages_per_seq=16,
            max_batch_size=2,
            prefill_buckets=(16,),
            quantize="int8",
        ),
        model_cfg=LATENT_CFG,
    )
    out = eng.generate([[1, 2, 3, 4]], None)
    assert len(out) == 1 and len(out[0]) >= 1


def test_v3_shaped_moe_mla_checkpoint_roundtrip(tmp_path):
    """A scaled-down DeepSeek-V3-shaped config (MLA + q_lora + sigmoid
    noaux_tc MoE with router_bias + shared expert) must roundtrip through
    the HF naming (kv_a_proj_with_mqa, e_score_correction_bias, experts)
    with identical logits."""
    from opsagent_tpu.models.config import MLAConfig, MoEConfig
    from opsagent_tpu.models.loader import load_checkpoint, save_checkpoint

    cfg = dataclasses.replace(
        get_config_preset("tiny-mla"),
        num_layers=3,
        moe=MoEConfig(
            num_experts=4,
            num_experts_per_token=2,
            num_shared_experts=1,
            expert_intermediate_size=32,
            norm_topk_prob=True,
            routed_scaling_factor=2.5,
            scoring_func="sigmoid",
            n_group=2,
            topk_group=1,
        ),
        moe_layer_start=1,
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(3), dtype=DTYPE)
    # Non-zero selection bias so the roundtrip must preserve it to keep
    # routing identical.
    params["moe_layers"]["router_bias"] = jnp.asarray(
        np.linspace(-1, 1, 2 * 4).reshape(2, 4), jnp.float32
    )
    ckpt = tmp_path / "model.safetensors"
    save_checkpoint(str(ckpt), params, cfg=cfg)
    loaded = load_checkpoint(str(ckpt), cfg, dtype=DTYPE)
    tokens = jnp.array([[5, 6, 7, 8, 9, 10]], jnp.int32)
    l1 = llama.forward_full(params, cfg, tokens, dtype=DTYPE)
    l2 = llama.forward_full(loaded, cfg, tokens, dtype=DTYPE)
    np.testing.assert_allclose(
        np.asarray(l1), np.asarray(l2), rtol=1e-5, atol=1e-5
    )


def test_latent_engine_prefix_cache_reuse():
    """Latent pages participate in the prefix cache: a second request
    sharing a prompt prefix gets cache hits and identical greedy output."""
    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams
    from opsagent_tpu.utils.perf import get_perf_stats

    eng = Engine(
        EngineConfig(
            model="tiny-mla",
            dtype=DTYPE,
            num_pages=64,
            page_size=4,
            max_pages_per_seq=16,
            max_batch_size=2,
            prefill_buckets=(16,),
        ),
        model_cfg=LATENT_CFG,
    )
    prompt = list(range(1, 13))  # 12 tokens = 3 full pages
    sp = SamplingParams(temperature=0.0, max_tokens=4)
    get_perf_stats().reset()
    sid1 = eng.add_request(list(prompt), sp)
    out1 = []
    while not eng.sequences[sid1].done and len(out1) < 4:
        out1 += eng.step_block([sid1]).get(sid1, [])
    out1 += [t for v in eng.drain().values() for t in v]
    eng.finish(sid1)  # donates full pages to the prefix trie

    sid2 = eng.add_request(list(prompt), sp)
    stats = get_perf_stats().get_stats()
    hits = stats.get("engine.prefix_hit_tokens", {}).get("count", 0)
    assert hits >= 1, stats.keys()
    out2 = []
    while not eng.sequences[sid2].done and len(out2) < 4:
        out2 += eng.step_block([sid2]).get(sid2, [])
    out2 += [t for v in eng.drain().values() for t in v]
    assert out1[:4] == out2[:4]


def test_mla_ring_attention_prefill_matches_oracle(params):
    """MLA under sequence-parallel ring attention (sp=2): the decoupled-
    rope q/k and padded v ride the ppermute KV ring unchanged."""
    from opsagent_tpu.parallel.mesh import make_mesh
    from opsagent_tpu.parallel.ring import make_ring_attention

    mesh = make_mesh(tp=2, dp=1, sp=2)
    ring = make_ring_attention(mesh)
    tokens = jax.random.randint(
        jax.random.PRNGKey(2), (1, 16), 0, CFG.vocab_size
    )
    ref = llama.forward_full(params, CFG, tokens, dtype=DTYPE)
    with mesh:
        out = jax.jit(
            lambda p, t: llama.forward_full(
                p, CFG, t, dtype=DTYPE, prefill_attn=ring
            )
        )(params, tokens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=3e-4, atol=3e-4
    )


# -- the latent pages under the streaming kernel ------------------------------
def _mixed_steps_then_blocks(eng):
    """A short row decodes while a longer prompt is admitted in chunks of a
    mixed step (a decode row's 1 slot beside a chunk's 8 in one ragged
    call), then both decode in fused blocks (the decode form)."""
    from opsagent_tpu.serving.sampler import SamplingParams

    short, long_ = [257, 9, 8, 7], [257] + list(range(1, 30))
    a = eng.add_request(short, SamplingParams(max_tokens=12))
    b = eng.begin_request(long_, SamplingParams(max_tokens=8))
    mixed = 0
    while b in eng._prefilling:
        done, total = eng.prefill_progress(b)
        eng.step_mixed([a], {b: min(total - done, 8)})
        mixed += 1
    assert mixed >= 3
    while not (eng.sequences[a].done and eng.sequences[b].done):
        eng.step_block(
            [s for s in (a, b) if not eng.sequences[s].done])
    return [eng.finish(a), eng.finish(b)]


@pytest.mark.parametrize("model", ["tiny-mla", "tiny-glm-flash"])
def test_latent_engine_under_the_streaming_kernel_matches_the_gathers(
    stream_kernel, model
):
    """The absorbed attention over latent pages through the one Pallas
    kernel (interpreted: ``conftest.stream_kernel``), a page fetched once
    as keys and values alike: mixed steps and fused decode blocks give the
    gather engine's tokens, at MLA alone and beside an expert share."""
    from opsagent_tpu.serving.engine import Engine, EngineConfig

    model_cfg = get_config_preset(model)
    if not model_cfg.mla.latent_cache:
        model_cfg = LATENT_CFG
    cfg = dict(
        model=model, dtype=DTYPE, tp=1, page_size=4, num_pages=64,
        max_pages_per_seq=16, max_batch_size=2, prefill_buckets=(8, 16),
        decode_block=4, mixed_buckets=(8,), max_step_tokens=16, seed=0,
    )
    want = _mixed_steps_then_blocks(
        Engine(EngineConfig(**cfg), model_cfg=model_cfg))
    with stream_kernel():
        eng = Engine(EngineConfig(**cfg), model_cfg=model_cfg)
        info = eng.impl_info()
        assert (info["attn_impl"], info["kv_page_form"]) == (
            "pallas-stream", "merged")
        got = _mixed_steps_then_blocks(eng)
    assert got == want and all(got)
