"""The configuration of a patterned model with experts held against
published (``models/config.py``), its HF round trip, and what an engine
refuses for a model with recurrent state."""

import dataclasses
import json
import os

import jax.numpy as jnp
import pytest

from opsagent_tpu.models import llama
from opsagent_tpu.models.config import (
    PRESETS, ModelConfig, config_from_hf, hf_config_dict, scaled_for_test,
)
from opsagent_tpu.serving.engine import BackendRefused, Engine, EngineConfig

SOLAR = PRESETS["solar-open2-250b"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_48_layer_preset_counts_250b_in_all_and_15b_active():
    assert 249e9 < SOLAR.num_params() < 251.5e9
    assert 14.4e9 < SOLAR.num_params(active=True) < 15.1e9
    # one chip's share of eight: its 40 experts a layer, the router whole
    share = dataclasses.replace(
        SOLAR, moe=dataclasses.replace(SOLAR.moe, num_experts=40))
    experts = 48 * 280 * 3 * 4096 * 1280
    assert SOLAR.num_params() - share.num_params() == experts
    assert share.moe.router_width == 320


@pytest.mark.parametrize("name", ["tiny-test", "tiny-moe", "tiny-hybrid"])
def test_num_params_is_the_trees_size(name):
    import jax

    cfg = PRESETS[name]
    tree = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    assert cfg.num_params() == sum(x.size for x in jax.tree.leaves(tree))


def test_solar_open2_round_trips_through_its_hf_config(tmp_path):
    hf = hf_config_dict(SOLAR)
    assert hf["model_type"] == "solar_open2"
    assert hf["gqa_layers"] == list(range(0, 48, 4)) and hf["gqa_interval"] == 3
    assert hf["use_rope"] is False and hf["use_gqa_gate"] is True
    assert "experts_held" not in hf
    (tmp_path / "config.json").write_text(json.dumps(hf))
    assert config_from_hf(str(tmp_path), name=SOLAR.name) == SOLAR
    # a chip's share keeps the router's width and says what it holds
    share = dataclasses.replace(SOLAR, num_layers=8, moe=dataclasses.replace(
        SOLAR.moe, num_experts=40, first_expert=80))
    hf = hf_config_dict(share)
    assert (hf["n_routed_experts"], hf["experts_held"],
            hf["first_expert_held"]) == (320, 40, 80)
    (tmp_path / "config.json").write_text(json.dumps(hf))
    assert config_from_hf(str(tmp_path), name=SOLAR.name) == share


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_catalogs_config_keys_give_the_preset(tmp_path):
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"Solar-Open2-250B"' in line)
    (tmp_path / "config.json").write_text(json.dumps(row["config"]))
    got = config_from_hf(str(tmp_path), name=SOLAR.name)
    assert got == SOLAR
    back = hf_config_dict(got)
    for key, value in row["config"].items():
        assert back[key] == value, key


def test_a_pattern_that_is_not_periodic_is_refused(tmp_path):
    hf = dict(hf_config_dict(SOLAR), gqa_layers=[0, 5, 8])
    (tmp_path / "config.json").write_text(json.dumps(hf))
    with pytest.raises(ValueError, match="periodic"):
        config_from_hf(str(tmp_path))
    with pytest.raises(ValueError, match="whole periods"):
        dataclasses.replace(SOLAR, num_layers=6)
    with pytest.raises(ValueError, match="linear_attn unset"):
        ModelConfig(name="x", vocab_size=8, hidden_size=8, intermediate_size=8,
                    num_layers=2, num_heads=1, num_kv_heads=1,
                    mixer_period=("attn", "linear"))


def test_scaled_for_test_keeps_whole_periods():
    tiny = scaled_for_test(SOLAR, periods=2)
    assert tiny.num_layers == 8 and tiny.vocab_size == 512
    assert [tiny.mixer_of(i) for i in range(8)] == [
        "attn", "linear", "linear", "linear"] * 2
    assert tiny.count_mixers("attn") == 2 and tiny.count_mixers("linear") == 6
    v3 = scaled_for_test(PRESETS["deepseek-v3"], periods=2)
    assert v3.num_layers == 3 + 2          # the dense layers, then two periods
    assert scaled_for_test(PRESETS["tiny-test"]).num_layers == 2


def test_period_runs_and_the_run_layout():
    import jax

    assert llama.period_runs(PRESETS["tiny-test"]) == ()
    assert llama.period_runs(SOLAR) == (
        ("r0_attn", "attn", 1), ("r1_linear", "linear", 3))
    cfg = dataclasses.replace(PRESETS["tiny-hybrid"], num_layers=8)
    whole = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    by_run = {k: v for k, v in whole.items() if k != "moe_layers"}
    for p in range(2):
        for key, _mixer, _n in llama.period_runs(cfg):
            by_run[f"moe_layers:{p}:{key}"] = {
                name: jnp.array(leaf[p])
                for name, leaf in whole["moe_layers"][key].items()}
    back = llama.stack_layer_runs(cfg, by_run)
    assert jax.tree.structure(back) == jax.tree.structure(whole)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(whole)):
        assert (a == b).all()
    assert llama.stack_layer_runs(cfg, whole) is whole


# -- what carries page chains alone refuses such a model, by name ----------------
def engine_cfg(**kw) -> EngineConfig:
    return EngineConfig(
        model="tiny-hybrid", dtype=jnp.float32, tp=1, max_batch_size=2,
        num_pages=16, max_pages_per_seq=8, prefill_buckets=(32,),
        mixed_buckets=(16,), **kw)


@pytest.mark.parametrize("kw,said", [
    ({"tp": 2}, "tp=2"),
    ({"offload": True}, "offload=True"),
    ({"weight_stream": "pallas-dma", "quantize": "int8"}, "pallas-dma"),
])
def test_an_engine_refuses_what_cannot_carry_the_state(kw, said):
    with pytest.raises(BackendRefused, match="linear-attention layers") as e:
        Engine(dataclasses.replace(engine_cfg(), **kw))
    assert said in str(e.value)


def test_an_engine_takes_the_streaming_kernel(stream_kernel):
    """Refused until PR 29 out of caution, not by a finding: the state-slot
    columns never reach attention (``_row_state``) and the GQA layers are
    plain GQA to the op (NoPE and the gate sit outside it)."""
    from opsagent_tpu.serving.sampler import SamplingParams

    with stream_kernel():
        eng = Engine(engine_cfg())
        assert eng.impl_info()["attn_impl"] == "pallas-stream"
        out = eng.generate([[257, 3, 1, 4, 1, 5, 9, 2, 6]],
                           SamplingParams(max_tokens=3))
    assert len(out[0]) == 3


@pytest.fixture(scope="module")
def engine():
    return Engine(engine_cfg())


def test_impl_info_reports_the_state(engine):
    info = engine.impl_info()
    assert (info["state_dtype"], info["state_slots"],
            info["state_snapshots"]) == ("float32", 2, 4)
    plain = Engine(dataclasses.replace(engine_cfg(), model="tiny-test"))
    assert "state_dtype" not in plain.impl_info()
    assert set(plain.cache) == {"k", "v"}


def test_the_snapshot_writer_and_the_page_store_are_refused(engine, tmp_path):
    with pytest.raises(BackendRefused, match="snapshot/writer.py"):
        engine.snapshot(str(tmp_path))
    with pytest.raises(BackendRefused, match="fleet page store"):
        engine.pagestore = object()
    engine.pagestore = None


def test_the_loader_refuses_a_solar_open2_checkpoint(tmp_path):
    from opsagent_tpu.models.loader import load_checkpoint

    (tmp_path / "config.json").write_text(json.dumps(hf_config_dict(
        dataclasses.replace(PRESETS["tiny-hybrid"]))))
    with pytest.raises(NotImplementedError, match="tensor names"):
        load_checkpoint(str(tmp_path), PRESETS["tiny-hybrid"], jnp.float32)


# -- Olmo-Hybrid-7B: dense, attention LAST in the period, post-norm (PR 33) ------
OLMO = PRESETS["olmo-hybrid-7b"]
TINY_OLMO = PRESETS["tiny-olmo-hybrid"]


def test_the_olmo_hybrid_preset_counts_7_43b():
    assert 7.425e9 < OLMO.num_params() < 7.435e9
    assert OLMO.num_params(active=True) == OLMO.num_params()     # dense
    d, la = 3840, OLMO.linear_attn
    assert (la.key_size, la.value_size, la.decay_size) == (2880, 5760, 30)
    mixer = d * (2 * 2880 + 3 * 5760) + 4 * 11520       # q k v o gate, conv
    gates = 2 * d * 30 + 30 + 30 + 192                  # a, b, A_log, dt, norm
    ffn = 3 * d * 11008
    linear, full = mixer + gates + ffn + 2 * d, 4 * d * d + 2 * d + ffn + 2 * d
    assert OLMO.num_params() == (
        24 * linear + 8 * full + 2 * 100352 * d + d)
    assert (OLMO.count_mixers("linear"), OLMO.count_mixers("attn")) == (24, 8)
    assert OLMO.head_dim_ == 128 and not OLMO.use_rope and OLMO.post_norm


@pytest.mark.parametrize("name", ["tiny-olmo-hybrid"])
def test_num_params_is_the_trees_size_with_full_rank_gates(name):
    import jax

    cfg = PRESETS[name]
    tree = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    assert cfg.num_params() == sum(x.size for x in jax.tree.leaves(tree))
    linear = tree["layers"]["r0_linear"]
    assert linear["wa"].shape == (2, 3, 64, 4)          # one decay a head
    assert linear["dt_bias"].shape == (2, 3, 4)
    assert linear["wog"].shape == (2, 3, 64, 96)        # full rank
    assert not {"f_down", "f_up", "g_down", "g_up"} & set(linear)
    assert tree["layers"]["r1_attn"]["qn"].shape == (2, 1, 64)   # whole width
    specs = llama.param_specs(cfg)
    assert jax.tree.structure(specs) == jax.tree.structure(
        jax.tree.map(lambda x: 0, tree))


@pytest.mark.parametrize("cfg", [OLMO, TINY_OLMO], ids=lambda c: c.name)
def test_olmo_hybrid_round_trips_through_its_hf_config(cfg, tmp_path):
    hf = hf_config_dict(cfg)
    assert hf["model_type"] == "olmo_hybrid"
    assert hf["layer_types"][:4] == ["linear_attention"] * 3 + ["full_attention"]
    assert hf["rope_parameters"] == {"rope_theta": None}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    assert config_from_hf(str(tmp_path), name=cfg.name) == cfg


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_catalogs_olmo_hybrid_keys_give_the_preset(tmp_path):
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if '"Olmo-Hybrid-7B"' in line)
    (tmp_path / "config.json").write_text(json.dumps(row["config"]))
    got = config_from_hf(str(tmp_path), name=OLMO.name)
    assert got == OLMO
    assert got.mixer_period == ("linear", "linear", "linear", "attn")
    back = hf_config_dict(got)
    for key, value in row["config"].items():
        assert back[key] == value, key


@pytest.mark.parametrize("change,said", [
    ({"layer_types": ["sliding_attention"] * 8}, "layer_types"),
    ({"layer_types": ["full_attention"] * 3}, "layer_types"),
    ({"linear_num_key_heads": 2}, "grouped"),
])
def test_an_olmo_hybrid_config_the_engine_cannot_run_is_refused(
        change, said, tmp_path):
    hf = dict(hf_config_dict(TINY_OLMO), **change)
    (tmp_path / "config.json").write_text(json.dumps(hf))
    with pytest.raises(ValueError, match=said):
        config_from_hf(str(tmp_path))


def test_a_period_that_ends_in_attention_runs_linear_first():
    assert llama.period_runs(OLMO) == (
        ("r0_linear", "linear", 3), ("r1_attn", "attn", 1))
    # a rope_theta in the file turns the rotary embedding on
    hf = dict(hf_config_dict(TINY_OLMO), rope_parameters={"rope_theta": 5e5})
    assert _from(hf).use_rope and _from(hf).rope_theta == 5e5
    # all-attention layer_types are the period of one
    hf = dict(hf_config_dict(TINY_OLMO), layer_types=["full_attention"] * 8)
    assert _from(hf).mixer_period == ("attn",) and not _from(hf).has_state
    with pytest.raises(ValueError, match="decay"):
        dataclasses.replace(TINY_OLMO.linear_attn, decay="row")
    with pytest.raises(ValueError, match="solar_open2"):
        hf_config_dict(dataclasses.replace(
            PRESETS["tiny-hybrid"], linear_attn=dataclasses.replace(
                PRESETS["tiny-hybrid"].linear_attn, decay="head")))


def _from(hf: dict) -> ModelConfig:
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        with open(os.path.join(d, "config.json"), "w") as f:
            json.dump(hf, f)
        return config_from_hf(d, name="x")


def test_the_loader_refuses_an_olmo_hybrid_checkpoint(tmp_path):
    from opsagent_tpu.models.loader import load_checkpoint

    (tmp_path / "config.json").write_text(
        json.dumps(hf_config_dict(TINY_OLMO)))
    with pytest.raises(NotImplementedError, match="olmo_hybrid"):
        load_checkpoint(str(tmp_path), TINY_OLMO, jnp.float32)
    # ... and the post-norm block alone, whatever its mixers
    with pytest.raises(NotImplementedError, match="tensor names"):
        load_checkpoint(str(tmp_path), dataclasses.replace(
            PRESETS["tiny-test"], post_norm=True), jnp.float32)
