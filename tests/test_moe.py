"""MoE layer stack (DeepSeek-style): routing math, oracle equivalence of the
serving paths, engine generation, and sharded execution on the 8-device mesh.
Capability target: BASELINE.json config 3 (DeepSeek function calling)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from opsagent_tpu.models import llama
from opsagent_tpu.models.config import get_config_preset


CFG = get_config_preset("tiny-moe")


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def test_param_tree_shapes(params):
    m = CFG.moe
    Lm = CFG.num_layers - CFG.moe_layer_start
    fe = m.expert_intermediate_size
    assert params["layers"]["wg"].shape[0] == CFG.moe_layer_start
    assert params["moe_layers"]["eg"].shape == (
        Lm, m.num_experts, CFG.hidden_size, fe
    )
    assert params["moe_layers"]["router"].shape == (
        Lm, CFG.hidden_size, m.num_experts
    )
    assert params["moe_layers"]["sg"].shape == (
        Lm, CFG.hidden_size, fe * m.num_shared_experts
    )
    # Specs tree must mirror the params tree exactly.
    jax.tree.map(lambda a, b: None, params, llama.param_specs(CFG))


def test_router_topk_normalized(params):
    """Top-k combine weights are nonnegative, sum to 1, with exactly k live."""
    lp = jax.tree.map(lambda a: a[0], params["moe_layers"])
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 5, CFG.hidden_size))
    m = CFG.moe
    logits = h.astype(jnp.float32) @ lp["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, m.num_experts_per_token)
    w = vals / vals.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)
    assert (np.asarray(vals) > 0).all()


def test_prefill_decode_match_forward_full(params):
    """The serving path (prefill + N decode steps) must reproduce the
    all-positions oracle through the MoE stack."""
    rng = np.random.default_rng(0)
    n = 12
    toks = rng.integers(1, CFG.vocab_size, n).astype(np.int32)

    # Oracle: all-positions logits.
    full = llama.forward_full(
        params, CFG, jnp.asarray(toks[None, :]), dtype=jnp.float32
    )

    # Serving: prefill 8, then 4 decode steps.
    P, NP, MaxP = 4, 16, 8
    cache = llama.make_cache(CFG, NP, P, dtype=jnp.float32)
    table = np.full((1, MaxP), -1, np.int32)
    table[0, :4] = [0, 1, 2, 3]
    buck = np.zeros((1, 16), np.int32)
    buck[0, :8] = toks[:8]
    logits, cache = llama.prefill(
        params, CFG, jnp.asarray(buck), jnp.asarray([8], jnp.int32),
        cache, jnp.asarray(table), dtype=jnp.float32,
    )
    np.testing.assert_allclose(
        np.asarray(logits[0]), np.asarray(full[0, 7]), rtol=2e-4, atol=2e-4
    )
    for i in range(8, n):
        logits, cache = llama.decode_step(
            params, CFG, jnp.asarray([toks[i]], jnp.int32),
            jnp.asarray([i], jnp.int32), cache, jnp.asarray(table),
            jnp.asarray([True]), dtype=jnp.float32,
        )
        np.testing.assert_allclose(
            np.asarray(logits[0]), np.asarray(full[0, i]),
            rtol=2e-4, atol=2e-4,
        )


def test_engine_generates_with_moe():
    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    eng = Engine(EngineConfig(
        model="tiny-moe", dtype=jnp.float32, page_size=8, num_pages=64,
        max_pages_per_seq=8, max_batch_size=2, prefill_buckets=(16, 32),
    ))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 500, 10).tolist(), rng.integers(1, 500, 20).tolist()]
    outs = eng.generate(prompts, SamplingParams(temperature=0.0, max_tokens=5))
    assert all(1 <= len(o) <= 5 for o in outs)
    # Greedy determinism through the MoE stack (fresh engine, same prompts).
    outs2 = eng.generate(prompts, SamplingParams(temperature=0.0, max_tokens=5))
    assert outs == outs2


def test_moe_checkpoint_roundtrip(tmp_path, params):
    """save_checkpoint must emit the full MoE tree (router, experts, shared)
    in DeepSeek HF naming, and load_checkpoint must rebuild it exactly."""
    from opsagent_tpu.models.loader import load_checkpoint, save_checkpoint

    path = str(tmp_path / "moe.safetensors")
    save_checkpoint(path, params)
    reloaded = load_checkpoint(path, CFG, dtype=jnp.float32)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-6, atol=1e-6,
        ),
        params,
        reloaded,
    )


def test_moe_aux_loss_reported():
    from opsagent_tpu.parallel.mesh import make_mesh
    from opsagent_tpu.training import TrainConfig, init_train_state, make_train_step

    mesh = make_mesh(tp=1, dp=1, sp=1, devices=jax.devices()[:1])
    tc = TrainConfig(remat=False)
    params, opt_state = init_train_state(
        CFG, tc, mesh, jax.random.PRNGKey(0), dtype=jnp.float32
    )
    step = make_train_step(CFG, tc, mesh, dtype=jnp.float32)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(1, 500, (2, 16)), jnp.int32
    )
    _, _, metrics = step(params, opt_state, tokens, jnp.ones((2, 16)))
    aux = float(metrics["moe_aux"])
    # Switch aux is >= 1 (equality at perfectly uniform routing), summed
    # over the MoE layers.
    assert aux >= 1.0


def test_sharded_moe_training_step():
    """Full training step over tiny-moe on the virtual 8-device mesh: the
    expert TP shardings must compile and produce a finite loss."""
    from opsagent_tpu.parallel.mesh import make_mesh
    from opsagent_tpu.training import TrainConfig, init_train_state, make_train_step

    mesh = make_mesh(tp=2, dp=2, sp=2)
    tc = TrainConfig(remat=True)
    params, opt_state = init_train_state(
        CFG, tc, mesh, jax.random.PRNGKey(0), dtype=jnp.float32
    )
    step = make_train_step(CFG, tc, mesh, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(1, 500, (4, 32)), jnp.int32)
    mask = jnp.ones((4, 32), jnp.float32)
    params, opt_state, metrics = step(params, opt_state, tokens, mask)
    loss = float(metrics["loss"])
    assert np.isfinite(loss)


class TestCombineWeightSemantics:
    """Combine-weight flags must match the checkpoint's HF config: DeepSeek-
    MoE-16B/V2-Lite ship norm_topk_prob=false (raw softmax probs); V3 ships
    norm_topk_prob=true with routed_scaling_factor=2.5 (advisor finding:
    unconditional renormalization corrupts DeepSeek-16B generation)."""

    def _outputs(self, flags, h, params):
        from dataclasses import replace

        cfg = replace(CFG, moe=replace(CFG.moe, **flags))
        lp = jax.tree.map(lambda a: a[0], params["moe_layers"])
        out, _ = llama._moe_mlp(h, lp, cfg)
        return np.asarray(out)

    def test_raw_vs_renormalized_differ_by_topk_mass(self, params):
        h = jax.random.normal(
            jax.random.PRNGKey(3), (2, 4, CFG.hidden_size), jnp.float32
        )
        raw = self._outputs({"norm_topk_prob": False}, h, params)
        renorm = self._outputs({"norm_topk_prob": True}, h, params)
        # Renormalization divides combine weights by sum(top-k probs) < 1,
        # so the routed contribution grows; outputs must differ.
        assert not np.allclose(raw, renorm)

    def test_routed_scaling_factor_scales_routed_path(self, params):
        h = jax.random.normal(
            jax.random.PRNGKey(4), (1, 3, CFG.hidden_size), jnp.float32
        )
        base = self._outputs({}, h, params)
        scaled = self._outputs({"routed_scaling_factor": 2.5}, h, params)
        # Shared-expert path is unscaled; isolate the routed path by diff.
        shared_only = self._outputs({"routed_scaling_factor": 0.0}, h, params)
        np.testing.assert_allclose(
            scaled - shared_only, 2.5 * (base - shared_only),
            rtol=2e-5, atol=2e-6,
        )

    def test_deepseek_16b_preset_uses_raw_probs(self):
        cfg = get_config_preset("deepseek-moe-16b")
        assert cfg.moe.norm_topk_prob is False
        assert cfg.moe.routed_scaling_factor == 1.0


class TestGroupedDispatch:
    """VERDICT item 7: expert FLOPs must scale with top-k, not E. The
    grouped capacity dispatch must reproduce the all-experts scan exactly
    when capacity covers every assignment."""

    def _cfg(self, **flags):
        from dataclasses import replace

        return replace(CFG, moe=replace(CFG.moe, **flags))

    def test_grouped_matches_scan_when_capacity_covers(self, params):
        lp = jax.tree.map(lambda a: a[0], params["moe_layers"])
        h = jax.random.normal(
            jax.random.PRNGKey(7), (4, 16, CFG.hidden_size), jnp.float32
        )
        # capacity_factor E/k => C == T: nothing can drop; outputs exact.
        scan_cfg = self._cfg(grouped_dispatch_min_tokens=0)
        grp_cfg = self._cfg(
            grouped_dispatch_min_tokens=1,
            capacity_factor=CFG.moe.num_experts / CFG.moe.num_experts_per_token,
        )
        want, aux_w = llama._moe_mlp(h, lp, scan_cfg)
        got, aux_g = llama._moe_mlp(h, lp, grp_cfg)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
        )
        np.testing.assert_allclose(float(aux_w), float(aux_g), rtol=1e-6)

    def test_grouped_flops_scale_with_capacity(self):
        """Regression guard on the actual compiled program: XLA cost
        analysis of the grouped path must report far fewer FLOPs than the
        all-experts scan on the same shapes (the VERDICT item's point —
        expert compute scales with top-k*capacity, not num_experts)."""
        # DeepSeek-shaped expert count (E >> k) — at tiny-moe's E=4, k=2
        # the dispatch bookkeeping outweighs the expert saving; the FLOPs
        # win this guards is the many-experts regime (config 3 is k=6 of
        # E=64).
        from dataclasses import replace

        from opsagent_tpu.models.config import MoEConfig

        def cfg_with(**flags):
            return replace(CFG, moe=MoEConfig(
                num_experts=16, num_experts_per_token=2,
                num_shared_experts=1, expert_intermediate_size=64, **flags,
            ))

        params = llama.init_params(
            cfg_with(), jax.random.PRNGKey(0), jnp.float32
        )
        lp = jax.tree.map(lambda a: a[0], params["moe_layers"])
        h = jax.random.normal(
            jax.random.PRNGKey(8), (8, 16, CFG.hidden_size), jnp.float32
        )

        def flops_of(cfg):
            fn = jax.jit(lambda h, lp: llama._moe_mlp(h, lp, cfg)[0])
            cost = fn.lower(h, lp).compile().cost_analysis()
            if isinstance(cost, list):  # older jax returns one per device
                cost = cost[0]
            return float(cost["flops"])

        grouped = cfg_with(
            grouped_dispatch_min_tokens=1, capacity_factor=1.25
        )
        grouped_flops = flops_of(grouped)
        # The scan path is useless as a cost baseline (XLA cost analysis
        # counts a while-loop body once, not per trip), so compare against
        # the ANALYTIC all-experts expert compute: E * T * 3 matmuls of
        # [d, fe]. Grouped runs E * C slots with C = ceil(T*k/E * cf), or
        # ~0.16x here — assert well under the dense count, which fails if
        # the path regresses to computing every expert on every token.
        m = grouped.moe
        T = 8 * 16
        dense_expert_flops = (
            m.num_experts * T * 3 * 2 * CFG.hidden_size
            * m.expert_intermediate_size
        )
        assert grouped_flops < 0.5 * dense_expert_flops, (
            grouped_flops, dense_expert_flops
        )
        out, _ = llama._moe_mlp(h, lp, grouped)
        assert out.shape == h.shape
        assert not np.isnan(np.asarray(out)).any()

    def test_decode_shapes_use_scan(self, params):
        """Below the threshold (decode: T = batch) the scan path runs —
        verified by behavior: outputs must be identical regardless of
        capacity_factor (which only affects the grouped path)."""
        lp = jax.tree.map(lambda a: a[0], params["moe_layers"])
        h = jax.random.normal(
            jax.random.PRNGKey(9), (4, 1, CFG.hidden_size), jnp.float32
        )
        a, _ = llama._moe_mlp(h, lp, self._cfg(capacity_factor=0.01))
        b, _ = llama._moe_mlp(h, lp, self._cfg(capacity_factor=100.0))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sharded_moe_training_step_grouped_dispatch():
    """The grouped capacity dispatch must also compile and train on the
    8-device (dp, sp, tp) mesh — the scatter/gather crosses the sp-sharded
    token axis, so XLA inserts the collectives."""
    from dataclasses import replace

    from opsagent_tpu.parallel.mesh import make_mesh
    from opsagent_tpu.training import TrainConfig, init_train_state, make_train_step

    cfg = replace(
        CFG, moe=replace(CFG.moe, grouped_dispatch_min_tokens=1,
                         capacity_factor=2.0),
    )
    mesh = make_mesh(tp=2, dp=2, sp=2)
    tc = TrainConfig(remat=True)
    params, opt_state = init_train_state(
        cfg, tc, mesh, jax.random.PRNGKey(0), dtype=jnp.float32
    )
    step = make_train_step(cfg, tc, mesh, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(1, 500, (4, 32)), jnp.int32)
    mask = jnp.ones((4, 32), jnp.float32)
    params, opt_state, metrics = step(params, opt_state, tokens, mask)
    assert np.isfinite(float(metrics["loss"]))


class TestExpertParallelism:
    """The ep mesh axis (parallel/mesh.py): expert weights and the grouped
    dispatch's per-expert buckets shard over ep, so MoE compute scales out
    across devices (the DeepSeek-V3-class configuration). Results must be
    bit-compatible with the unsharded oracle — ep is a layout, not math."""

    def _forward(self, mesh):
        from opsagent_tpu.parallel.mesh import shard_params

        params = llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(1, 500, (2, 32)), jnp.int32
        )
        if mesh is None:
            return llama.forward_full(params, CFG, tokens, dtype=jnp.float32)
        sharded = shard_params(params, llama.param_specs(CFG), mesh)
        with mesh:
            return jax.jit(
                lambda p, t: llama.forward_full(p, CFG, t, dtype=jnp.float32)
            )(sharded, tokens)

    def test_ep2_forward_matches_oracle(self):
        from opsagent_tpu.parallel.mesh import make_mesh

        want = self._forward(None)
        got = self._forward(make_mesh(ep=2, dp=2, tp=2))
        assert jnp.allclose(want, got, atol=1e-4), float(
            jnp.max(jnp.abs(want - got))
        )

    def test_ep4_grouped_dispatch_matches(self):
        """Force the grouped (capacity-bucketed) dispatch under ep=4 — the
        path whose buckets actually shard over the expert axis."""
        from dataclasses import replace

        from opsagent_tpu.parallel.mesh import make_mesh, shard_params

        cfg = replace(CFG, moe=replace(CFG.moe, grouped_dispatch_min_tokens=1))
        params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        tokens = jnp.asarray(
            np.random.default_rng(1).integers(1, 500, (2, 32)), jnp.int32
        )
        want = llama.forward_full(params, cfg, tokens, dtype=jnp.float32)
        mesh = make_mesh(ep=4, dp=1, tp=2)
        sharded = shard_params(params, llama.param_specs(cfg), mesh)
        with jax.set_mesh(mesh):
            got = jax.jit(
                lambda p, t: llama.forward_full(p, cfg, t, dtype=jnp.float32)
            )(sharded, tokens)
        assert jnp.allclose(want, got, atol=1e-4), float(
            jnp.max(jnp.abs(want - got))
        )

    def test_ep_training_step_finite(self):
        from opsagent_tpu.parallel.mesh import make_mesh
        from opsagent_tpu.training import (
            TrainConfig,
            init_train_state,
            make_train_step,
        )

        mesh = make_mesh(ep=2, dp=2, tp=2)
        tc = TrainConfig(remat=True)
        params, opt_state = init_train_state(
            CFG, tc, mesh, jax.random.PRNGKey(0), dtype=jnp.float32
        )
        step = make_train_step(CFG, tc, mesh, dtype=jnp.float32)
        tokens = jnp.asarray(
            np.random.default_rng(2).integers(1, 500, (4, 16)), jnp.int32
        )
        _, _, metrics = step(params, opt_state, tokens, jnp.ones((4, 16)))
        assert np.isfinite(float(metrics["loss"]))

    def test_engine_generates_under_ep(self):
        from opsagent_tpu.serving.engine import Engine, EngineConfig

        eng = Engine(EngineConfig(
            model="tiny-moe", dtype=jnp.float32, tp=2, ep=2,
            num_pages=128, page_size=8, max_pages_per_seq=16,
            max_batch_size=2, prefill_buckets=(16,),
        ))
        out = eng.generate([[1, 2, 3, 4], [5, 6, 7]], None)
        assert len(out) == 2 and all(len(t) >= 1 for t in out)

    def test_ep_constrain_pins_layout_under_mesh(self):
        """_ep_constrain must actually apply inside jit under
        `jax.set_mesh` (the context the trainer's step runs in; the
        legacy `with mesh:` leaves get_abstract_mesh empty, which would
        silently turn the constraint into dead code)."""
        from opsagent_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(ep=2, dp=2, tp=2)
        P = jax.sharding.PartitionSpec
        with jax.set_mesh(mesh):
            y = jax.jit(
                lambda x: llama._ep_constrain(x, P("ep", None))
            )(jnp.ones((4, 8)))
        assert "ep" in str(y.sharding.spec)

        # ...and stay a no-op with no mesh context at all.
        z = jax.jit(
            lambda x: llama._ep_constrain(x, P("ep", None))
        )(jnp.ones((4, 8)))
        assert "ep" not in str(z.sharding)


def test_sigmoid_router_with_bias_and_groups():
    """DeepSeek-V3 routing semantics (noaux_tc): sigmoid scores, the
    e_score_correction_bias steers SELECTION only (combine weights use
    raw sigmoid scores), and group-limited top-k confines selection to
    the best topk_group expert groups."""
    import dataclasses

    import numpy as np

    from opsagent_tpu.models import llama
    from opsagent_tpu.models.config import MoEConfig, get_config_preset

    base = get_config_preset("tiny-moe")
    cfg = dataclasses.replace(
        base,
        moe=MoEConfig(
            num_experts=4,
            num_experts_per_token=2,
            num_shared_experts=0,
            expert_intermediate_size=8,
            scoring_func="sigmoid",
            n_group=2,
            topk_group=1,
            grouped_dispatch_min_tokens=7777,  # force all-experts scan
        ),
    )
    d, fe, E = cfg.hidden_size, 8, 4
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((1, 3, d)), jnp.float32)
    lp = {
        # Router logits biased so experts 0 and 2 (in DIFFERENT groups)
        # score highest pre-bias.
        "router": jnp.asarray(
            np.stack([
                np.full((d,), 0.05), np.full((d,), -0.05),
                np.full((d,), 0.04), np.full((d,), -0.04),
            ], axis=1), jnp.float32,
        ),
        "router_bias": jnp.zeros((E,), jnp.float32),
        "eg": jnp.asarray(rng.standard_normal((E, d, fe)) * 0.1, jnp.float32),
        "eu": jnp.asarray(rng.standard_normal((E, d, fe)) * 0.1, jnp.float32),
        "ed": jnp.asarray(rng.standard_normal((E, fe, d)) * 0.1, jnp.float32),
    }
    out_nobias, _ = llama._moe_mlp(h, lp, cfg)

    # A large selection bias on group 1's experts (ids 2,3) must flip the
    # chosen GROUP — changing the output — while zero bias keeps it.
    lp_biased = dict(lp, router_bias=jnp.asarray(
        [0.0, 0.0, 50.0, 50.0], jnp.float32
    ))
    out_biased, _ = llama._moe_mlp(h, lp_biased, cfg)
    assert not np.allclose(np.asarray(out_nobias), np.asarray(out_biased))

    # Bias steers selection only: with selection UNCHANGED (bias uniform
    # across experts), outputs are identical — combine weights ignore it.
    lp_uniform = dict(lp, router_bias=jnp.full((E,), 7.0, jnp.float32))
    out_uniform, _ = llama._moe_mlp(h, lp_uniform, cfg)
    np.testing.assert_allclose(
        np.asarray(out_nobias), np.asarray(out_uniform), rtol=1e-6, atol=1e-6
    )
