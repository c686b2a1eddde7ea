"""The two forms KV pages are held in (``ops.attention.page_form``): split
``[.., P, K, D]`` and merged ``[.., P, K*D]``, the same bytes in the same
order. CPU, float32 compute: what is checked is that every token written
is the token read, in either form, through every reader; that a tp shard
of merged pages is whole heads; and that pages leave the device (host
tier, snapshot manifest, fleet transfer records) in the one split format
whatever is held. The tile arithmetic that chooses a form is the
compiler's (tests/test_tpu_compile_steps.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opsagent_tpu.models import llama
from opsagent_tpu.models.config import TINY_MLA, TINY_TEST
from opsagent_tpu.ops.attention import (
    QuantizedPages,
    causal_prefill_attention,
    page_form,
    page_view,
    paged_decode_attention,
    paged_ragged_attention,
    pages_merged,
    write_kv_pages,
)
from opsagent_tpu.parallel.mesh import make_mesh, shard_params
from opsagent_tpu.serving.engine import Engine, EngineConfig
from opsagent_tpu.serving.fleet.transfer import pack_entries, unpack_entries
from opsagent_tpu.serving.sampler import SamplingParams

TINY_MLA_LATENT = dataclasses.replace(
    TINY_MLA, mla=dataclasses.replace(TINY_MLA.mla, latent_cache=True)
)
N, P, MAXP = 12, 4, 4      # pages, slots a page, pages a sequence
LAYERS, LAYER = 3, 1       # the layer-stacked form, and the layer written


# -- which form --------------------------------------------------------------
@pytest.mark.parametrize("kv_heads,impl,form", [
    (1, "xla", "split"),        # MLA's latent; one head of a tp shard
    (2, "xla", "merged"),       # the 72B's 8 heads over tp=4
    (3, "xla", "merged"),
    (4, "xla", "merged"),       # the 7B: a T(4,128) tile if split
    (6, "xla", "merged"),
    (7, "xla", "merged"),
    (8, "xla", "split"),        # the 72B on one chip: the tile is full
    (16, "xla", "split"),
    (1, "pallas-stream", "split"),    # one head: the same bytes, a unit axis
    (2, "pallas-stream", "merged"),   # a kv head is a lane slice of the row
    (3, "pallas-stream", "merged"),
    (4, "pallas-stream", "merged"),
    (6, "pallas-stream", "merged"),
    (7, "pallas-stream", "merged"),
    (8, "pallas-stream", "merged"),   # at any head count: nothing is gathered
    (16, "pallas-stream", "merged"),
])
def test_page_form_by_kv_heads_and_backend(kv_heads, impl, form):
    """The form a reader's pages are held in, and what the kernel's
    dispatch makes of each form at that head count: it takes the one
    ``page_form`` gives it and refuses the other by name (the gather reads
    either, by the trailing axis)."""
    from opsagent_tpu.ops.attention import _require_form

    assert page_form(kv_heads, impl) == form
    D = 8
    held = {
        "split": jnp.zeros((N, P, kv_heads, D)),
        "merged": jnp.zeros((N, P, kv_heads * D)),
    }
    if impl == "xla":
        assert pages_merged(held[form], D) == (form == "merged")
        return
    _require_form(held[form], D)
    if kv_heads > 1:    # one head is the same bytes either way
        with pytest.raises(ValueError, match="split pages"):
            _require_form(held["split"], D)
        # ... unless the array's heads are tp shards of one head each.
        _require_form(held["split"], D, tp=kv_heads)


@pytest.mark.parametrize("kv_shards,form", [(1, "merged"), (2, "split")])
def test_cache_form_counts_the_heads_of_one_shard(kv_shards, form):
    assert TINY_TEST.num_kv_heads == 2
    assert llama.cache_form(TINY_TEST, kv_shards) == form
    # the latent is one head whatever the shards: one merged row a token
    assert llama.cache_form(TINY_MLA_LATENT, kv_shards) == "merged"


# -- every token written is the token read -----------------------------------
def _pages(form: str, kv: str, layered: bool, K: int, D: int):
    lead = (LAYERS, N) if layered else (N,)
    row = (K * D,) if form == "merged" else (K, D)
    if kv == "int8":
        return QuantizedPages(
            jnp.zeros(lead + (P,) + row, jnp.int8),
            jnp.ones(lead + (P, K), jnp.float32),
        )
    return jnp.zeros(lead + (P,) + row, jnp.bfloat16)


CASES = [
    (K, form, kv, layered)
    for K in (1, 2, 4, 8)
    for form in (("split",) if K == 1 else ("split", "merged"))
    for kv in ("bf16", "int8")
    for layered in (False, True)
]


@pytest.mark.parametrize("K,form,kv,layered", CASES)
def test_write_then_read_matches_attention_over_the_same_tokens(
    K, form, kv, layered
):
    """Two chunks are written through the page table (the second with rows
    of different valid lengths, padded columns, an unassigned page and an
    inert row), then the ragged reader over the second chunk and the
    decode reader at the last token must equal causal attention over the
    same keys and values held contiguously."""
    G = 2
    D = 24 if K == 1 else 8          # one wide head: the MLA latent's shape
    B, S1, S2 = 3, 5, 6
    keys = jax.random.split(jax.random.PRNGKey(K), 3)
    valid2 = np.array([6, 3, 0])     # row 2 is inert in the second chunk
    total = S1 + valid2
    T = S1 + S2
    # Keys and values in the pages' own dtype, so bf16 pages hold them
    # exactly and both sides round the softmax to it alike.
    held = jnp.float32 if kv == "int8" else jnp.bfloat16
    q, k, v = (
        jax.random.normal(kk, (B, T, h, D)).astype(dt)
        for kk, h, dt in zip(keys, (K * G, K, K), (jnp.float32, held, held))
    )
    # Rows own scattered pages; row 1's last page is unassigned (-1): its
    # tokens are dropped, and it has none there (5 + 3 = 8 = two pages).
    table = jnp.asarray(
        [[7, 2, 9, -1], [0, 5, -1, -1], [3, 11, 4, -1]], jnp.int32
    )
    kp, vp = _pages(form, kv, layered, K, D), _pages(form, kv, layered, K, D)
    layer = jnp.int32(LAYER) if layered else None
    start0 = jnp.zeros((B,), jnp.int32)
    kp, vp = write_kv_pages(
        kp, vp, k[:, :S1], v[:, :S1], table, start0, layer=layer
    )
    start = jnp.full((B,), S1, jnp.int32)
    kp, vp = write_kv_pages(
        kp, vp, k[:, S1:], v[:, S1:], table, start,
        valid_len=jnp.asarray(valid2), layer=layer,
    )
    assert pages_merged(kp, D) == (form == "merged")

    # Nothing landed outside the rows' pages, nor in another layer.
    values = kp.q if kv == "int8" else kp
    touched = np.abs(np.asarray(values, np.float32)).reshape(
        values.shape[: values.ndim - (2 if form == "merged" else 3)] + (-1,)
    ).sum(-1) > 0                                  # [(L,) N]
    own = np.zeros(N, bool)
    own[[7, 2, 9, 0, 5, 3, 11]] = True             # pages holding a token
    if layered:
        assert not touched[[0, 2]].any()
        touched = touched[LAYER]
    np.testing.assert_array_equal(touched, own)

    want = causal_prefill_attention(q, k, v, lengths=jnp.asarray(total))
    tol = dict(atol=0.08, rtol=0.08) if kv == "int8" else dict(atol=1e-4)
    got = paged_ragged_attention(
        q[:, S1:], kp, vp, table, start, jnp.asarray(valid2), layer=layer
    )
    for b in range(B):
        np.testing.assert_allclose(
            got[b, : valid2[b]], want[b, S1 : total[b]], **tol
        )
    last = jnp.asarray(total - 1)
    got1 = paged_decode_attention(
        q[jnp.arange(B), last], kp, vp, table, jnp.asarray(total),
        layer=layer,
    )
    np.testing.assert_allclose(got1, want[jnp.arange(B), last], **tol)


@pytest.mark.parametrize("kv", ["", "int8"])
def test_merged_and_split_caches_hold_the_same_bytes(kv):
    """One prefill into a cache of each form: a merged page array is the
    split one reshaped, scale planes and all."""
    cfg = TINY_TEST
    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 500)
    lengths = jnp.asarray([8, 5])
    table = jnp.asarray([[3, 1, -1], [0, 6, -1]], jnp.int32)
    out = {}
    for form in ("split", "merged"):
        cache = llama.make_cache(
            cfg, 8, 4, jnp.float32, kv_quantize=kv, form=form
        )
        out[form] = llama.prefill(
            params, cfg, tokens, lengths, cache, table, dtype=jnp.float32
        )
    np.testing.assert_array_equal(out["split"][0], out["merged"][0])
    for a, b in zip(
        jax.tree.leaves(out["split"][1]), jax.tree.leaves(out["merged"][1])
    ):
        assert a.shape[:3] == b.shape[:3]
        np.testing.assert_array_equal(
            np.asarray(a).reshape(b.shape), np.asarray(b)
        )
        np.testing.assert_array_equal(
            np.asarray(page_view(b, a.shape[2:])), np.asarray(a)
        )


def test_the_mla_latent_cache_is_one_merged_row_on_whole_lanes():
    """Held: ``[L, N, P, page_dim]``, the latent padded to the 128 lanes
    and no unit kv-head axis (either made the chip's compiler copy the
    whole cache a step), and one number a token for the placeholder ``v``.
    Off the device a page is ``[P, 1, page_dim]`` (the split form)."""
    m = TINY_MLA_LATENT.mla
    assert (m.latent_dim, m.page_dim) == (40, 128)
    L = TINY_MLA_LATENT.num_layers
    cache = llama.make_cache(TINY_MLA_LATENT, 8, 4, jnp.float32)
    assert cache["k"].shape == (L, 8, 4, 128) and cache["v"].shape == (L, 8, 4)
    wire = llama.make_cache(TINY_MLA_LATENT, 8, 4, jnp.float32, form="split")
    assert wire["k"].shape == (L, 8, 4, 1, 128)
    assert wire["v"].shape == (L, 8, 4, 1, 1)
    int8 = llama.make_cache(TINY_MLA_LATENT, 8, 4, jnp.float32, "int8")
    assert int8["k"].q.shape == (L, 8, 4, 128) and int8["k"].q.dtype == jnp.int8
    assert int8["k"].scale.shape == (L, 8, 4)       # one scale a token


# -- tensor parallel: a shard of merged pages is whole heads -----------------
@pytest.mark.parametrize("kv", ["", "int8"])
@pytest.mark.parametrize("tp", [2, 4])
def test_tp_shards_of_merged_pages_are_whole_heads(tp, kv):
    """8 kv heads over tp=2/4 leave 4/2 a shard: held merged, the K*D axis
    sharded over tp. The mixed step on the 8-device mesh must match the
    single-device split cache, and each shard's pages must be exactly its
    own heads of it."""
    assert len(jax.devices()) == 8, "conftest must force 8 CPU devices"
    cfg = dataclasses.replace(TINY_TEST, num_heads=8, num_kv_heads=8)
    K, D = 8, cfg.head_dim_
    form = llama.cache_form(cfg, tp)
    assert form == "merged" and llama.cache_form(cfg) == "split"
    params = llama.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 6), 0, 500)
    start, qlens = jnp.asarray([0, 0]), jnp.asarray([6, 4])
    table = jnp.asarray([[2, 5, -1], [7, 0, -1]], jnp.int32)

    def run(p, c):
        return llama.mixed_step(
            p, cfg, tokens, start, qlens, c, table, dtype=jnp.float32
        )

    ref_logits, ref_cache = run(
        params, llama.make_cache(cfg, 8, 4, jnp.float32, kv_quantize=kv)
    )
    mesh = make_mesh(tp=tp, dp=8 // tp)
    specs = llama.cache_specs(cfg, kv_quantize=kv, form=form)
    values = specs["k"].q if kv else specs["k"]
    assert tuple(values) == (None, None, None, "tp")
    cache = shard_params(
        llama.make_cache(cfg, 8, 4, jnp.float32, kv_quantize=kv, form=form),
        specs, mesh,
    )
    sharded = shard_params(params, llama.param_specs(cfg), mesh)
    with mesh:
        logits, cache = jax.jit(run)(sharded, cache)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), rtol=2e-3, atol=2e-3
    )
    ref_k = ref_cache["k"].q if kv else ref_cache["k"]       # [L, N, P, K, D]
    got_k = cache["k"].q if kv else cache["k"]               # [L, N, P, K*D]
    per = K // tp
    tol = dict(atol=1) if kv else dict(rtol=2e-3, atol=2e-3)
    for shard in got_k.addressable_shards:
        first = shard.index[-1].start // D                   # its first head
        assert shard.data.shape[-1] == per * D
        np.testing.assert_allclose(
            np.asarray(shard.data, np.float32).reshape(
                shard.data.shape[:3] + (per, D)
            ),
            np.asarray(ref_k[..., first : first + per, :], np.float32),
            **tol,
        )


# -- off the device a page is [P, K, D], whatever is held --------------------
BASE = dict(
    model="tiny-test", dtype=jnp.float32, tp=1, page_size=4,
    num_pages=64, max_pages_per_seq=16, max_batch_size=4,
    prefill_buckets=(16, 32), decode_block=4, seed=0,
)
PROMPT = [257, 72, 101, 108, 108, 111, 44, 32, 119]


def _engine(monkeypatch, form: str, **kw):
    """An engine holding its pages in ``form``: "merged" is what tiny-test
    (2 kv heads) gets; "split" is the format every PR before 25 held."""
    with monkeypatch.context() as m:
        if form == "split":
            m.setattr(llama, "cache_form", lambda *a, **k: "split")
        eng = Engine(EngineConfig(**{**BASE, **kw}))
    assert eng.impl_info()["kv_page_form"] == form
    return eng


def _generate_and_park(eng):
    sid = eng.add_request(PROMPT, SamplingParams(max_tokens=7))
    while not eng.sequences[sid].done:
        eng.step_block([sid])
    hist = PROMPT + eng.finish(sid)
    assert eng.park_chain(hist) > 0
    eng.offload_flush()
    return hist


@pytest.mark.parametrize("kvq", ["", "int8"])
def test_spilled_pages_and_transfer_records_keep_the_split_format(
    monkeypatch, kvq
):
    """The same session on an engine of each form: the host pool's entries
    and the fleet's transfer records (shapes, bytes, digests) are equal, so
    either can restore from the other's; restored into the merged engine
    the session continues as the split one does."""
    pools, records, hists, engines = {}, {}, {}, {}
    for form in ("split", "merged"):
        eng = engines[form] = _engine(
            monkeypatch, form, offload=True, kv_quantize=kvq
        )
        hists[form] = _generate_and_park(eng)
        pools[form] = eng.offload.pool.match(hists[form])
        records[form] = pack_entries(pools[form])
    assert hists["split"] == hists["merged"]
    assert records["split"] == records["merged"]
    K, D = TINY_TEST.num_kv_heads, TINY_TEST.head_dim_
    for entry in pools["merged"]:
        k = entry.data["k"].q if kvq else entry.data["k"]
        assert k.shape == (TINY_TEST.num_layers, 4, K, D)

    # The split engine's records, imported by a fresh merged engine.
    eng = _engine(monkeypatch, "merged", offload=True, kv_quantize=kvq)
    for tokens, tree in unpack_entries(records["split"], eng.cache):
        assert eng.offload.pool.put(tokens, tree)
    prompt2 = hists["split"] + [32, 110, 111, 119]
    outs = []
    for e in (eng, engines["split"]):
        sid = e.begin_request(prompt2, SamplingParams(max_tokens=4))
        assert e._prefilling[sid] >= len(pools["split"]) * e.cfg.page_size
        while not e.prefill_step(sid):
            pass
        while not e.sequences[sid].done:
            e.step_block([sid])
        outs.append(e.finish(sid))
    assert outs[0] == outs[1]


def test_the_snapshot_plan_records_split_pages_and_restores_the_held_form(
    tmp_path, monkeypatch
):
    plans = {}
    for form in ("split", "merged"):
        eng = _engine(monkeypatch, form)
        man = eng.snapshot(str(tmp_path / form))
        plans[form] = man["kv_plan"]
    assert plans["split"] == plans["merged"]
    shapes = {
        leaf["path"]: leaf["shape"] for leaf in plans["merged"]["leaves"]
    }
    K, D = TINY_TEST.num_kv_heads, TINY_TEST.head_dim_
    assert shapes["['k']"] == [TINY_TEST.num_layers, 64, 4, K, D]
    restored = Engine.from_snapshot(str(tmp_path / "merged"), warmup=False)
    assert restored.impl_info()["kv_page_form"] == "merged"
    assert restored.cache["k"].shape == (TINY_TEST.num_layers, 64, 4, K * D)
    greedy = SamplingParams(temperature=0.0, max_tokens=6)
    assert restored.generate([PROMPT], greedy) == eng.generate(
        [PROMPT], greedy
    )
