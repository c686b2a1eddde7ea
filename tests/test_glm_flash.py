"""GLM-4.7-Flash's shape through the program, at the tiny preset on seeded
weights, against the plain reference (``benchmarks/reference/
glm4_moe_lite.py``: float32, keys and values materialised per head, every
expert over every token, no cache): latent pages under heads whose value is
as wide as their query, one dense layer, then expert layers run as a share
with every expert held.

Both sides hold the same model exactly (int8 leaves times their scales, made
from the seed by ``families/glm4_moe_lite.py``) and compute in float32 on
the CPU, so what is compared differs only by the order of float32 sums:
the absorbed form against materialised heads, a sorted dispatch against a
sum over all experts. Tolerances: 5e-4 absolute on logits whose spread is
about 1 (forty times what the paths measure, test_mla.py's oracle tolerance);
int8 pages have their own, with its reason.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import server
from benchmarks import weights as W
from benchmarks.loading import load_data, load_family, load_module
from opsagent_tpu import obs
from opsagent_tpu.models import llama
from opsagent_tpu.models.config import (
    config_from_hf, get_config_preset, hf_config_dict,
)
from opsagent_tpu.ops.attention import QuantizedPages

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILE = os.path.join(ROOT, "benchmarks", "configs", "glm47-flash-l12-int8.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SEED = 2**31 + 40
TOL = dict(rtol=5e-4, atol=5e-4)
PAGE = 4


@pytest.fixture(scope="module")
def tiny() -> dict:
    return load_data(FILE, rehearse=True)


@pytest.fixture(scope="module")
def model(tiny):
    """(ModelConfig, the served tree, the family, the reference)."""
    family = load_family(tiny)
    mc = family.model_config(tiny)
    assert mc == get_config_preset("tiny-glm-flash")
    return mc, server.program_tree(tiny, SEED), family, load_module(
        "reference", tiny["reference"])


def reference_logits(tiny, model, tokens) -> np.ndarray:
    """The reference's logits [T, V] at every position of one sequence."""
    _, _, family, ref = model
    sz, root = family.sizes(tiny), W.root_key(SEED)
    x = W.embedding(root, family.LEAF_NO["embed"], sz["v"], sz["d"])[
        jnp.asarray(tokens)].astype(jnp.float32)
    tables = family.position_tables(ref, len(tokens), tiny, sz)
    for _key, kind, first, count in family.stacks(sz):
        for layer in range(first, first + count):
            w = {name: W.as_float32(leaf) for name, leaf in
                 family.layer_leaves(root, kind, layer, sz).items()}
            x = family.apply_layer(ref, kind, x, w, tables, tiny, sz)
    q, scale = W.matrix(root, family.LEAF_NO["lm_head"], 0, sz["d"], sz["v"])
    norm = W.norm(root, family.LEAF_NO["final_norm"], 0, sz["d"])
    return np.asarray(ref.logits(
        x, norm.astype(jnp.float32), W.dequantize(q, scale),
        tiny["rms_norm_eps"]))


def sequence(n: int, key: int = 6) -> np.ndarray:
    return np.asarray(jax.random.randint(jax.random.PRNGKey(key), (n,), 0, 500))


# -- the configuration --------------------------------------------------------------
@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_config_from_hf_and_back_round_trips_the_catalogs_keys(tmp_path):
    with open(CATALOG) as f:
        row = next(json.loads(x) for x in f if '"GLM-4.7-Flash"' in x)
    with open(tmp_path / "config.json", "w") as f:
        json.dump(row["config"], f)
    cfg = config_from_hf(str(tmp_path), name="glm-4.7-flash")
    assert cfg == get_config_preset("glm-4.7-flash")
    back = hf_config_dict(cfg)
    assert back["model_type"] == "glm4_moe_lite"
    # the drafting layer is not part of the served model: its key alone
    # does not come back
    differs = {k for k, v in row["config"].items() if back.get(k) != v}
    assert differs == {"num_nextn_predict_layers"}
    assert config_from_hf_dict(back, tmp_path) == cfg
    # a share of the experts is this engine's own pair of keys
    share = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=8, first_expert=16))
    assert config_from_hf_dict(hf_config_dict(share), tmp_path).moe == share.moe


def config_from_hf_dict(hf: dict, tmp_path):
    path = tmp_path / "again"
    path.mkdir(exist_ok=True)
    with open(path / "config.json", "w") as f:
        json.dump(hf, f)
    return config_from_hf(str(path), name="glm-4.7-flash")


@pytest.mark.parametrize("change,said", [
    ({"topk_method": "greedy"}, "topk_method"),
    ({"n_group": 4}, "group-limited"),
    ({"partial_rotary_factor": 0.5}, "partial rotary"),
    ({"num_key_value_heads": 2}, "grouped kv heads"),
])
def test_config_from_hf_refuses_what_the_layer_cannot_run(tmp_path, change, said):
    hf = dict(hf_config_dict(get_config_preset("tiny-glm-flash")), **change)
    with pytest.raises(ValueError, match=said):
        config_from_hf_dict(hf, tmp_path)


def test_num_params_counts_the_latent_projections():
    """29.9 B in all (the model card's 30B). What one token uses: 3.26 B
    outside the embedding and the head, the card's "A3B" (ISSUE 40 says
    about 3.2 B: 2% under this count), 3.90 B with both tables, which is
    what ``active=True`` has always counted."""
    cfg = get_config_preset("glm-4.7-flash")
    assert abs(cfg.num_params() / 29.9e9 - 1) < 0.01
    tables = 2 * cfg.vocab_size * cfg.hidden_size
    body = cfg.num_params(active=True) - tables
    assert abs(body / 3.26e9 - 1) < 0.01 and abs(body / 3.2e9 - 1) < 0.025
    # by hand, one layer's attention: 21.8 M
    d, H = 2048, 20
    attn = (d * 768 + 768 + 768 * H * 256 + d * 576 + 512
            + 512 * H * (192 + 256) + H * 256 * d)
    assert attn == 21_759_232
    one = dataclasses.replace(cfg, num_layers=1, moe=None, moe_layer_start=0)
    assert one.num_params() == attn + 3 * d * 10240 + 2 * d + tables + d
    # DeepSeek-V3's published 671 B comes out of the same count
    assert abs(get_config_preset("deepseek-v3").num_params() / 671e9 - 1) < 0.01


# -- the expert layer ---------------------------------------------------------------
def test_the_share_with_every_expert_held_is_the_references_expert_layer(
        tiny, model):
    """``_moe_share`` at ``router_experts == num_experts``: the shared
    expert counted once, the weights renormalised and scaled by 1.8, and
    the selection bias moving WHICH experts are chosen and not their
    weights (a bias of 10 on one expert puts it in every token's choice;
    its weight stays its own score's share)."""
    mc, _, family, ref = model
    sz, root = family.sizes(tiny), W.root_key(SEED)
    w = {name: W.as_float32(leaf) for name, leaf in
         family.layer_leaves(root, "experts", 1, sz).items()}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, sz["d"]), jnp.float32)
    assert mc.moe.routed_scaling_factor == 1.8 and mc.moe.norm_topk_prob
    for bias in (w["router_bias"], w["router_bias"].at[5].set(10.0)):
        lp = dict(w, router_bias=bias)
        want = jnp.stack([
            ref.expert_ffn(seq, lp, top_k=sz["k"], scale=1.8,
                           eps=tiny["rms_norm_eps"])
            for seq in x])
        u = llama.rms_norm(x, lp["mlp_norm"], mc.rms_norm_eps)
        got, stats = llama._moe_share(u, lp, mc, None)
        np.testing.assert_allclose(x + got, want, **TOL)
        # every assignment lands here: nothing is absent from a whole layer
        assert (int(stats[1]), int(stats[2])) == (2 * 9 * sz["k"], 0)
    _, idx, vals = llama._route(u, lp, mc)
    assert bool(jnp.all(jnp.any(idx == 5, axis=-1)))
    np.testing.assert_allclose(jnp.sum(vals, -1), 1.8, rtol=1e-5)
    s = jax.nn.sigmoid(u @ lp["router"])
    picked = jnp.take_along_axis(s, idx, -1)
    np.testing.assert_allclose(
        vals, 1.8 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)


# -- prefill, then decode through latent pages --------------------------------------
def test_rows_prefill_then_decode_sit_on_the_references_logits(tiny, model):
    mc, params, _, _ = model
    tokens = sequence(14)
    want = reference_logits(tiny, model, tokens)
    cache = llama.make_cache(mc, 16, PAGE, jnp.float32)
    assert cache["k"].shape == (3, 16, PAGE, 128) and "stats" in cache
    table = jnp.asarray([[2, 5, 7, 11]], jnp.int32)
    logits, cache = llama.prefill(
        params, mc, jnp.asarray(tokens[None, :6]), jnp.asarray([6]), cache,
        table, dtype=jnp.float32)
    np.testing.assert_allclose(logits[0], want[5], **TOL)
    for t in range(6, 14):
        logits, cache = llama.decode_step(
            params, mc, jnp.asarray(tokens[t:t + 1]), jnp.asarray([t]), cache,
            table, active=jnp.asarray([True]), dtype=jnp.float32)
        np.testing.assert_allclose(
            logits[0], want[t], err_msg=f"position {t}", **TOL)
    # the share's counters ride in the cache of a model without state:
    # two expert layers a pass, 9 passes
    assert int(cache["stats"][0]) == 2 * 9


def test_a_packed_mixed_step_sits_on_the_references_logits(tiny, model):
    """Two rows in one packed program (4 rows x 8 slots over 16 step
    tokens): a chunk of 7 behind 5 cached tokens and a decode row."""
    mc, params, _, _ = model
    a, b = sequence(12, 7), sequence(9, 8)
    want_a = reference_logits(tiny, model, a)
    want_b = reference_logits(tiny, model, b)
    assert llama.pack_widths(4 * 8, 16) == (16, 8)
    cache = llama.make_cache(mc, 16, PAGE, jnp.float32)
    table = jnp.asarray([[1, 3, 5, -1], [0, 2, 4, -1], [-1] * 4, [-1] * 4],
                        jnp.int32)

    def step(rows, start, q_lens, cache):
        toks = np.zeros((4, 8), np.int32)
        for i, r in enumerate(rows):
            toks[i, :len(r)] = r
        return llama.mixed_step(
            params, mc, jnp.asarray(toks), jnp.asarray(start),
            jnp.asarray(q_lens), cache, table, dtype=jnp.float32,
            step_tokens=16)

    _, cache = step([a[:5], b[:8]], [0, 0, 0, 0], [5, 8, 0, 0], cache)
    logits, cache = step([a[5:12], b[8:9]], [5, 8, 0, 0], [7, 1, 0, 0], cache)
    np.testing.assert_allclose(logits[0], want_a[11], **TOL)
    np.testing.assert_allclose(logits[1], want_b[8], **TOL)


def test_a_fused_decode_block_serves_the_references_first_choices(tiny, model):
    """Eight greedy passes under one scan, the latent cache its carry:
    every token it emits is the reference's own first choice given the
    tokens before it (a gap of zero, the number ``check`` compares)."""
    from opsagent_tpu.serving import decode_loop

    mc, params, _, _ = model
    prompt = sequence(6, 9)
    cache = llama.make_cache(mc, 16, PAGE, jnp.float32)
    table = jnp.asarray([[4, 9, 1, 6], [-1] * 4], jnp.int32)
    logits, cache = llama.prefill(
        params, mc, jnp.asarray(prompt[None]), jnp.asarray([6]), cache,
        table[:1], dtype=jnp.float32)
    first = int(jnp.argmax(logits[0]))
    tok, at = jnp.asarray([first, 0]), jnp.asarray([6, 0])
    active = jnp.asarray([True, False])
    toks, cache, _ = decode_loop.decode_block_carry(    # every lane seated anew
        params, mc, tok, at, jnp.zeros_like(active), jax.random.PRNGKey(0),
        jnp.ones_like(active), tok, at, active, jnp.asarray([8, 0]), cache,
        table, jnp.zeros((2,)), jnp.zeros((2,), jnp.int32), jnp.ones((2,)),
        jnp.int32(-1), jnp.int32(0), n_steps=8, greedy=True,
        dtype=jnp.float32)
    served = [first, *np.asarray(toks[0]).tolist()]
    want = reference_logits(tiny, model, [*prompt, *served[:-1]])
    gaps = [want[5 + i].max() - want[5 + i][t] for i, t in enumerate(served)]
    assert max(gaps) < 1e-4, gaps


# -- int8 latent pages --------------------------------------------------------------
def test_int8_latent_pages_read_back_what_was_written_to_a_part_in_127(
        tiny, model):
    """``QuantizedPages`` under the latent: one float32 scale a token (the
    absmax of its 128-wide row over 127), so a number read back is off by
    at most half a step of its own row, and the logits of prefill and
    decode through int8 pages stay within 2% of their spread of the float
    pages' (a scale an array, or none, errs tens of times that). Read at
    the model's first layer alone, the dense one: through an expert layer
    a rounding now and then flips a near-tied expert, which moves a logit
    by its whole spread and says nothing of the pages."""
    mc, params, _, _ = model
    mc = dataclasses.replace(mc, num_layers=1)
    tokens = sequence(12, 10)
    table = jnp.asarray([[3, 8, 0, 5]], jnp.int32)
    out = {}
    for kv in ("", "int8"):
        cache = llama.make_cache(mc, 16, PAGE, jnp.float32, kv_quantize=kv)
        logits, cache = llama.prefill_with_prefix(
            params, mc, jnp.asarray(tokens[None, :8]), jnp.asarray([0]),
            jnp.asarray([8]), cache, table, dtype=jnp.float32)
        got = [logits[0]]
        for t in range(8, 12):
            logits, cache = llama.decode_step(
                params, mc, jnp.asarray(tokens[t:t + 1]), jnp.asarray([t]),
                cache, table, active=jnp.asarray([True]), dtype=jnp.float32)
            got.append(logits[0])
        out[kv] = (np.stack(got), cache)
    pages = out["int8"][1]["k"]
    assert isinstance(pages, QuantizedPages)
    assert pages.q.shape == (1, 16, PAGE, 128) and pages.scale.shape == (1, 16, PAGE)
    # both runs wrote the same latents (the layer's input is the
    # embedding): read back, each is within half a step of its own row
    held = np.asarray(out[""][1]["k"])[0]
    back = (np.asarray(pages.q, np.float32)
            * np.asarray(pages.scale)[..., None])[0]
    step = np.abs(held).max(-1, keepdims=True) / 127.0
    assert np.all(np.abs(back - held) <= 0.5 * step + 1e-7)
    assert np.abs(held[3, :, :40]).min() > 0        # a page that was written
    spread = out[""][0].std()
    assert np.abs(out["int8"][0] - out[""][0]).max() < 0.02 * spread


# -- the engine: a trie hit over latent pages, the counters -------------------------
def test_engine_serves_a_trie_hit_over_latent_pages_and_counts(tiny, model):
    from benchmarks import check, tokens as T
    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    mc, params, _, _ = model

    def engine(**kw):
        return Engine(
            EngineConfig(model=tiny["preset"], dtype=jnp.float32, tp=1,
                         quantize="int8", max_batch_size=4, num_pages=128,
                         max_pages_per_seq=16, prefill_buckets=(64,),
                         mixed_buckets=(16,), **kw),
            model_cfg=mc, params=params, params_quantized=True,
            tokenizer=server.bench_tokenizer(mc.vocab_size))

    eng = engine()
    info = eng.impl_info()
    assert (info["attn_impl"], info["kv_page_form"], info["kv_write"]) == (
        "xla", "merged", "rows")
    rng = np.random.default_rng(4)
    system = {"role": "system", "content": T.decode(rng.integers(32, 127, 70))}
    asks = [T.template_ids([system, {"role": "user", "content": T.decode(
        rng.integers(32, 127, n))}]) for n in (21, 33)]
    hit0 = obs.PREFIX_HIT_TOKENS.value()
    live0 = obs.ATTN_CONTEXT_TOKENS.value(what="live")
    read0 = obs.ATTN_CONTEXT_TOKENS.value(what="read")
    greedy = SamplingParams(temperature=0.0, max_tokens=20)
    replies = [eng.generate([p], greedy)[0] for p in asks]
    # the second request found the first's system prompt in the trie: whole
    # pages of its 72 shared tokens
    assert obs.PREFIX_HIT_TOKENS.value() - hit0 >= 64
    samples = [{"prompt_ids": p, "reply_ids": [t for t in r if t != T.EOS],
                "constrained": False} for p, r in zip(asks, replies)]
    numbers = check.run_check(tiny, SEED, samples)
    ok, lines = check.verdict(numbers, tiny["check"]["limits"])
    assert ok and numbers["agree_share"] == 1.0, lines
    # what the reader was handed: the gather reads every row's whole table
    # (4 rows x 16 pages x 16 slots a pass), of which the two contexts of
    # about 100 tokens are a small share
    live = obs.ATTN_CONTEXT_TOKENS.value(what="live") - live0
    read = obs.ATTN_CONTEXT_TOKENS.value(what="read") - read0
    assert read > 0 and read % (4 * 16 * 16) == 0
    assert 0.02 < live / read < 0.25
    # the share's counters come off the device for a model without state
    before = obs.MOE_SHARE.value(what="moe_layer_passes")
    eng.sync_device_counters()
    assert obs.MOE_SHARE.value(what="moe_layer_passes") > before
    # int8 latent pages run, and say so
    quant = engine(kv_quantize="int8")
    assert quant.impl_info()["kv_quantize"] == "int8"
    assert isinstance(quant.cache["k"], QuantizedPages)
    assert len(quant.generate([asks[0]], greedy)[0]) > 0
    # the host tier copies every leaf of the cache by page: refused beside
    # the share's counters
    from opsagent_tpu.serving.engine import BackendRefused
    with pytest.raises(BackendRefused, match="expert share"):
        engine(offload=True)
