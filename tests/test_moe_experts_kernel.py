"""The grouped expert kernel (``ops/moe_experts_pallas.py``) against
``llama._moe_share``'s XLA loop, which stays its oracle: the same layer of
``tiny-glm-flash`` (8 of 8 experts held, top-2) and ``tiny-hybrid`` (4 of
8 held: half the assignments are absent) with int8 stacks through both,
the kernel interpreted on the CPU. What the two share (the router, the
sort, the counts) must agree bit for bit; the blocks' arithmetic within
the tolerance of the other kernels' parity tests (float32 activations
tightly: the kernel scales the float32 product where the loop scales the
weights, the same numbers in another order)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opsagent_tpu.models import llama
from opsagent_tpu.models.config import get_config_preset
from opsagent_tpu.ops import kernels
from opsagent_tpu.ops import moe_experts_pallas as grouped

KERNEL = grouped.IMPL
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    monkeypatch.setenv("OPSAGENT_PALLAS_INTERPRET", "1")


@pytest.fixture(scope="module")
def layers():
    """name -> (config, the layer's leaves for each way of holding them)."""
    out = {}
    glm = get_config_preset("tiny-glm-flash")
    stack = llama.init_params_random_quantized(glm, 0)["moe_layers"]
    out["glm-slice"] = glm, jax.tree.map(lambda a: a[1], stack)
    out["glm-whole"] = glm, llama._LayerView(stack, (1,), True)
    hyb = get_config_preset("tiny-hybrid")
    stack = llama.init_params_random_quantized(hyb, 0)["moe_layers"]["r1_linear"]
    out["hybrid-whole"] = hyb, llama._LayerView(stack, (0, 2), True)
    return out


def _share(impl: str, h, lp, cfg, valid):
    return jax.jit(
        lambda h, valid: llama._moe_share(h, lp, cfg, valid, impl))(h, valid)


def _inputs(cfg, tokens: int, dtype, padded: bool, seed: int = 0):
    """``tokens`` tokens in one row, or 256 in four; ``padded``: three more
    slots a row that ``token_valid`` keeps out."""
    rows = 4 if tokens == 256 else 1
    per = tokens // rows
    slots = per + 3 * padded
    h = jax.random.normal(
        jax.random.PRNGKey(seed), (rows, slots, cfg.hidden_size)).astype(dtype)
    valid = jnp.broadcast_to(
        jnp.arange(slots) < per, (rows, slots)) if padded else None
    return h, valid


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("padded", [False, True], ids=["dense", "padded"])
@pytest.mark.parametrize("tokens", [1, 16, 37, 256])
@pytest.mark.parametrize("held", ["glm-slice", "glm-whole", "hybrid-whole"])
def test_the_kernel_is_the_loop(layers, held, tokens, padded, dtype):
    cfg, lp = layers[held]
    h, valid = _inputs(cfg, tokens, dtype, padded)
    want, want_stats = _share("xla", h, lp, cfg, valid)
    got, got_stats = _share(KERNEL, h, lp, cfg, valid)
    np.testing.assert_array_equal(np.asarray(got_stats), np.asarray(want_stats))
    assert got.dtype == want.dtype and got.shape == want.shape
    scale = float(np.abs(np.asarray(want, np.float32)).max()) or 1.0
    np.testing.assert_allclose(
        np.asarray(got, np.float32) / scale, np.asarray(want, np.float32) / scale,
        rtol=TOL[dtype], atol=TOL[dtype])
    # every assignment of a real token is counted, landed or absent
    assert int(got_stats[1]) + int(got_stats[2]) == (
        tokens * cfg.moe.num_experts_per_token)


@pytest.mark.parametrize("held", ["glm-slice", "glm-whole", "hybrid-whole"])
def test_an_expert_of_several_blocks_beside_experts_of_none(layers, held):
    """37 tokens that are one token: every assignment lands on the same
    two experts, 37 rows each in blocks of 16 (three blocks an expert, the
    last one part padding), and every other expert holds none."""
    cfg, lp = layers[held]
    one = jax.random.normal(jax.random.PRNGKey(3), (1, 1, cfg.hidden_size))
    h = jnp.broadcast_to(one, (1, 37, cfg.hidden_size))
    want, want_stats = _share("xla", h, lp, cfg, None)
    got, got_stats = _share(KERNEL, h, lp, cfg, None)
    names = dict(zip(llama.MOE_STATS, np.asarray(got_stats).tolist()))
    np.testing.assert_array_equal(np.asarray(got_stats), np.asarray(want_stats))
    assert names["experts_touched"] in (1, 2)       # tiny-hybrid holds half
    assert names["experts_touched"] < cfg.moe.num_experts
    assert names[llama.MOE_STATS[4]] == 37 > grouped.MIN_BLOCK_ROWS
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    # one token: every position's output is the first's
    np.testing.assert_allclose(
        np.asarray(got)[0], np.broadcast_to(np.asarray(got)[0, :1], (37, 64)),
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("tf", [8, 16])
def test_tiles_of_the_intermediate_width_accumulate(layers, monkeypatch, tf):
    """An expert wider than one weight tile (none of the tiny presets is;
    ``f_tile`` cuts the cells' only above 6 MB a matrix) runs ``f / tf``
    grid steps a block and sums their down products in the float32
    scratch: the same output as one step."""
    cfg, lp = layers["glm-whole"]
    h, valid = _inputs(cfg, 37, jnp.float32, True)
    whole, _ = _share(KERNEL, h, lp, cfg, valid)
    monkeypatch.setattr(grouped, "f_tile", lambda d, f: tf)
    tiled, _ = _share(KERNEL, h, lp, cfg, valid)
    want, _ = _share("xla", h, lp, cfg, valid)
    np.testing.assert_allclose(
        np.asarray(tiled), np.asarray(want), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(tiled), np.asarray(whole), rtol=2e-5, atol=2e-5)


def test_no_assignment_lands_here(layers):
    """A pass whose tokens are all padding uses no block: the kernel's
    grid is empty and the output is the shared expert's alone."""
    cfg, lp = layers["hybrid-whole"]
    h, _ = _inputs(cfg, 16, jnp.float32, False)
    valid = jnp.zeros(h.shape[:2], bool)
    want, want_stats = _share("xla", h, lp, cfg, valid)
    got, got_stats = _share(KERNEL, h, lp, cfg, valid)
    np.testing.assert_array_equal(np.asarray(got_stats), np.asarray(want_stats))
    assert int(got_stats[1]) == 0
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_the_kernel_refuses_stacks_it_cannot_read(layers):
    cfg, lp = layers["glm-slice"]
    wide = dict(lp, **{name: lp[name].dequantize() for name in ("eg", "eu", "ed")})
    h, _ = _inputs(cfg, 16, jnp.float32, False)
    with pytest.raises(ValueError, match="int8 QuantizedLinear"):
        _share(KERNEL, h, wide, cfg, None)
    got, _ = _share("xla", h, wide, cfg, None)      # the loop reads any
    assert got.shape == h.shape


def test_the_choice_and_what_the_engine_says_ran(monkeypatch):
    """``moe_experts_backend`` answers the kernel on a TPU for int8 stacks
    held whole at widths on the lanes, the loop everywhere else; the engine
    asks it once and ``impl_info()["moe_impl"]`` says what its programs
    run: here the loop, and under a choice that answers the kernel (the
    tests' handle, interpreted) the kernel, with the same greedy tokens."""
    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    glm = dict(hidden_size=2048, expert_width=1536)
    choice = kernels.moe_experts_backend
    assert choice(platform="tpu", quantize="int8", **glm) == KERNEL
    assert choice(platform="tpu", quantize="int8", hidden_size=4096,
                  expert_width=1280, tp=1, ep=1) == KERNEL
    # (each reason it answers the loop: tests/test_kernels.py FALLBACKS)
    assert set(kernels.MOE_BACKENDS) == {"xla", KERNEL}

    small = dict(
        model="tiny-glm-flash", quantize="int8", dtype=jnp.float32, tp=1,
        max_batch_size=2, num_pages=16, max_pages_per_seq=8,
        prefill_buckets=(32,), mixed_buckets=(16,), mixed_batching=True)
    prompts = [[257] + list(range(1, 20)), [257, 4, 4, 2]]

    def tokens(eng):
        return [list(t) for t in eng.generate(
            prompts, SamplingParams(max_tokens=6))]

    asked = []
    loop = Engine(EngineConfig(**small))
    assert loop.impl_info()["moe_impl"] == "xla"
    want = tokens(loop)
    del loop
    monkeypatch.setattr(
        kernels, "moe_experts_backend",
        lambda **kw: asked.append(kw) or KERNEL)
    traced = []
    blocks = grouped.moe_expert_blocks
    monkeypatch.setattr(
        grouped, "moe_expert_blocks",
        lambda *a, **kw: traced.append(kw) or blocks(*a, **kw))
    eng = Engine(EngineConfig(**small))
    assert eng.impl_info()["moe_impl"] == KERNEL
    assert asked == [dict(platform="cpu", quantize="int8", hidden_size=64,
                          expert_width=32, tp=1, ep=1)]
    assert tokens(eng) == want
    assert traced and all(kw["interpret"] for kw in traced)
    # an engine without an expert share says nothing of one
    assert "moe_impl" not in Engine(EngineConfig(**dict(
        small, model="tiny-test"))).impl_info()
