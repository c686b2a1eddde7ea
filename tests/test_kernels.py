"""``ops.kernels``: which kernels run is one value, ``Kernels``, chosen once
by ``choose_kernels`` from what an engine can observe and handed to every
step function as one argument. Here: what the chooser gives each benchmark
cell on a TPU, each reason a field falls back to XLA (alone: the other
fields keep their kernels), and that a step function obeys the value it is
handed with no engine and no context around it."""

import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from opsagent_tpu.models import llama
from opsagent_tpu.models.config import get_config_preset
from opsagent_tpu.ops import kernels
from opsagent_tpu.ops.kernels import Kernels, choose_kernels

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# What each cell's engine runs on a TPU (PERF.md section 3): attention
# reader, state kernel, expert blocks, weight stream.
CELLS = {
    "qwen25-7b-int8": Kernels("pallas-stream"),
    "qwen25-72b-l8-int8": Kernels("pallas-stream"),
    "solar-open2-ep8-l8-int8": Kernels(
        "pallas-stream", "pallas-state", "pallas-grouped"),
    "olmo-hybrid-7b-int8": Kernels("pallas-stream", "pallas-state"),
    "glm47-flash-l12-int8": Kernels(
        "pallas-stream", experts="pallas-grouped"),
    "jamba2-3b-int8": Kernels("pallas-stream", "pallas-ssm"),
}


def _cell(name: str):
    """(ModelConfig, what the chooser is told of the engine) of a file under
    benchmarks/configs, read as the benchmark's server reads it."""
    from benchmarks.loading import load_data, load_family

    config = load_data(os.path.join(
        ROOT, "benchmarks", "configs", name + ".json"))
    engine = config["engine"]
    return load_family(config).model_config(config), dict(
        tp=engine["tp"], ep=engine.get("ep", 1), dtype=engine["dtype"],
        quantize=engine.get("quantize", ""),
        kv_quantize=engine.get("kv_quantize", ""))


def test_the_table_names_every_configuration_file():
    files = glob.glob(os.path.join(ROOT, "benchmarks", "configs", "*.json"))
    assert sorted(CELLS) == sorted(
        os.path.basename(f)[:-len(".json")] for f in files)


@pytest.mark.parametrize("name", list(CELLS))
def test_the_chooser_gives_each_cell_the_kernels_it_runs(name):
    model_cfg, engine = _cell(name)
    assert choose_kernels(model_cfg, platform="tpu", **engine) == CELLS[name]
    assert choose_kernels(model_cfg, platform="cpu", **engine) == Kernels()


# -- each reason a field falls back to "xla", alone --------------------------
SOLAR = "solar-open2-ep8-l8-int8"      # a kernel in every chosen field
JAMBA = "jamba2-3b-int8"               # the other state kernel


def _wider(cfg, **moe):
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **moe))


FALLBACKS = {
    # reason: (cell, change to the model's config, change to what the engine
    #          observes, the fields that fall back)
    "the-cpu": (SOLAR, None, dict(platform="cpu"),
                ("attn", "state", "experts")),
    "tp-2": (SOLAR, None, dict(tp=2), ("experts",)),
    "ep-2": (SOLAR, None, dict(ep=2), ("experts",)),
    "int8-pages": (SOLAR, None, dict(kv_quantize="int8"), ("attn",)),
    "head-dim-off-the-lanes": (
        SOLAR, lambda c: dataclasses.replace(c, head_dim=64), {}, ("attn",)),
    "bfloat16-expert-stacks": (SOLAR, None, dict(quantize=""), ("experts",)),
    "int4-expert-stacks": (SOLAR, None, dict(quantize="int4"), ("experts",)),
    "expert-width-off-the-lanes": (
        SOLAR, lambda c: _wider(c, expert_intermediate_size=1504), {},
        ("experts",)),
    "model-width-off-the-lanes": (
        SOLAR, lambda c: dataclasses.replace(c, hidden_size=4000), {},
        ("experts",)),
    "bfloat16-state": (SOLAR, None, dict(state_dtype="bfloat16"), ("state",)),
    "ssm-on-the-cpu": (JAMBA, None, dict(platform="cpu"), ("attn", "state")),
    "ssm-bfloat16-state": (
        JAMBA, None, dict(state_dtype="bfloat16"), ("state",)),
    "ssm-channels-off-the-lanes": (
        JAMBA, lambda c: dataclasses.replace(
            c, mamba=dataclasses.replace(c.mamba, d_inner=5000)), {},
        ("state",)),
    "ssm-state-off-the-sublanes": (
        JAMBA, lambda c: dataclasses.replace(
            c, mamba=dataclasses.replace(c.mamba, d_state=12)), {},
        ("state",)),
}


@pytest.mark.parametrize("reason", list(FALLBACKS))
def test_a_field_falls_back_to_xla_alone(reason):
    """One thing changed from a cell that runs a kernel in every field it
    has: the fields named fall back to "xla" and no other moves."""
    cell, change, observed, fields = FALLBACKS[reason]
    model_cfg, engine = _cell(cell)
    if change is not None:
        model_cfg = change(model_cfg)
    got = choose_kernels(model_cfg, **{"platform": "tpu", **engine, **observed})
    assert got == CELLS[cell]._replace(**{f: "xla" for f in fields})


def test_a_name_that_is_no_kernels_is_an_error():
    kernels.require_kernels(Kernels())
    kernels.require_kernels(CELLS[SOLAR])
    for bad in (Kernels(experts="pallas"), Kernels(attn="pallas-dma"),
                Kernels(state="pallas-grouped"), Kernels(weights="dma")):
        with pytest.raises(ValueError, match="expected one of"):
            kernels.require_kernels(bad)


# -- a step function runs what it is handed, whatever surrounds it ----------------
def _traced(preset: str, handed: Kernels) -> str:
    """The jaxpr of ``llama.mixed_step`` over two rows of a 16-slot bucket,
    with no engine and no mesh or other context entered."""
    cfg = get_config_preset(preset)
    params = jax.eval_shape(
        lambda: llama.init_params_random_quantized(cfg, 0, jnp.float32))
    cache = jax.eval_shape(lambda: llama.make_cache(
        cfg, 8, 16, jnp.float32, state_slots=4 * cfg.has_state,
        state_impl=handed.state))
    table = jax.ShapeDtypeStruct(
        (2, 4 + llama.STATE_COLUMNS * cfg.has_state), jnp.int32)
    rows = jax.ShapeDtypeStruct((2,), jnp.int32)
    return str(jax.make_jaxpr(
        lambda p, t, s, q, c, tb: llama.mixed_step(
            p, cfg, t, s, q, c, tb, dtype=jnp.float32, kernels=handed)
    )(params, jax.ShapeDtypeStruct((2, 16), jnp.int32), rows, rows, cache,
      table))


@pytest.mark.parametrize("preset,handed,name", [
    ("tiny-glm-flash", Kernels(experts="pallas-grouped"), "moe_experts"),
    ("tiny-hybrid", Kernels(state="pallas-state"), "linear_state"),
    ("tiny-jamba", Kernels(state="pallas-ssm"), "selective_scan"),
])
def test_a_step_traced_with_no_engine_calls_the_kernel_it_is_handed(
        monkeypatch, preset, handed, name):
    """Before PR 45 the expert kernel reached ``_moe_share`` through a
    thread-local that only ``Engine.mesh_ctx`` entered: a step traced
    outside it got the loop in silence."""
    monkeypatch.setenv("OPSAGENT_PALLAS_INTERPRET", "1")
    text = _traced(preset, handed)
    assert "pallas_call" in text and name in text
    assert "pallas_call" not in _traced(preset, Kernels())
