"""The state kernel (``ops/linear_state_pallas.py``) in interpret mode on the
CPU against ``delta_rule_chunk`` / ``delta_rule_step`` from the same slots.

Float32 at this file's tolerance, which is ``tests/hybrid_state_common.py``'s:
the kernel runs the recurrence a token at a time where the chunk form solves
a block's triangular system, and folds ``beta`` into k and v as its square
root, so the two agree to float32's rounding and not to the bit. A state
held in bfloat16 between two passes errs more than ten times the tolerance
(asserted: the check on the chip cannot see that, PERF.md section 2).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opsagent_tpu.models import llama
from opsagent_tpu.models.config import PRESETS
from opsagent_tpu.ops import kernels
from opsagent_tpu.ops.kernels import Kernels
from opsagent_tpu.ops import linear_state_pallas as lsp
from opsagent_tpu.ops.linear_attention import delta_rule_chunk, delta_rule_step

TOL = 2e-4
PAGE = 16


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def release_compiled_programs():
    """As ``tests/hybrid_state_common.py``'s: an interpreted kernel is a large
    CPU program, and a process may hold only so many memory mappings."""
    yield
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            limit = int(f.read())
        with open("/proc/self/maps") as f:
            held = sum(1 for _ in f)
    except (OSError, ValueError):
        return
    if held > 0.4 * limit:
        import gc

        jax.clear_caches()
        gc.collect()


@pytest.fixture
def state_kernel():
    """The tests' handle on the state kernel off the chip, as
    ``conftest.stream_kernel`` is the attention kernel's: an engine built
    and run inside ``with state_kernel():`` holds its slots for the kernel
    and runs it interpreted. Nothing in the program can name it."""
    import contextlib

    @contextlib.contextmanager
    def under():
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(
                kernels, "linear_state_backend", lambda **_: "pallas-state")
            mp.setenv("OPSAGENT_PALLAS_INTERPRET", "1")
            yield

    return under


# -- the kernel against the two XLA forms, slot for slot --------------------------
# A batch of five rows: a whole bucket from a held state (and a snapshot), a
# decode lane, an idle row, a short fresh row without a slot, a whole bucket
# from a fresh slot.
LIVE = (3, 7, 5, -1, 9)
SNAP = (10, -1, 11, -1, -1)
FRESH = (False, False, False, True, True)
SLOTS, WIDTH = 12, 200


def _inputs(H, dk, dv, by_channel, S, seed=0, decay=0.5):
    B = len(LIVE)
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q, k = f(B, S, H, dk), f(B, S, H, dk)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.abs(f(B, S, H, dk) if by_channel else f(B, S, H)) * decay
    beta = jax.nn.sigmoid(f(B, S, H)) * 2
    return dict(
        q=q, k=k, v=f(B, S, H, dv), g=g, beta=beta,
        state=f(SLOTS, H, dk, dv),
        conv=jnp.asarray(rng.standard_normal(
            (SLOTS, *lsp.conv_slot_shape(WIDTH))), jnp.bfloat16),
        tail=jnp.asarray(rng.standard_normal((B, WIDTH)), jnp.bfloat16),
        valid=jnp.asarray([S, 1, 0, min(S, 3), S], jnp.int32),
        live=jnp.asarray(LIVE, jnp.int32), snap=jnp.asarray(SNAP, jnp.int32),
        fresh=jnp.asarray(FRESH),
    )


def _held(state, H, dk, dv):
    """[slots, H, dk, dv] as the kernel holds it, and back."""
    p = lsp.heads_packed(dv)
    n = state.shape[0]
    return state.reshape(n, H // p, p, dk, dv).transpose(0, 1, 3, 2, 4).reshape(
        n, H // p, dk, p * dv)


def _heads(held, H, dk, dv):
    p = lsp.heads_packed(dv)
    n = held.shape[0]
    return held.reshape(n, H // p, dk, p, dv).transpose(0, 1, 3, 2, 4).reshape(
        n, H, dk, dv)


def _oracle(x, state=None):
    """(o, new state a row) by the XLA forms from the rows' slots."""
    state = x["state"] if state is None else state
    B, S = x["q"].shape[:2]
    S0 = jnp.where(x["fresh"][:, None, None, None], 0.0,
                   state[jnp.clip(x["live"], 0)])
    if S > 1:
        return delta_rule_chunk(
            x["q"], x["k"], x["v"], x["g"], x["beta"], S0, x["valid"])
    on = x["valid"] > 0
    g = x["g"][:, 0]
    o, S1 = delta_rule_step(
        x["q"][:, 0], x["k"][:, 0], x["v"][:, 0],
        jnp.where(on.reshape(B, *([1] * (g.ndim - 1))), g, 0.0),
        jnp.where(on[:, None], x["beta"][:, 0], 0.0), S0)
    return o[:, None], S1


def _kernel(x, H, dk, dv, state=None):
    state = x["state"] if state is None else state
    o, held, conv = lsp.delta_rule_slots(
        x["q"], x["k"], x["v"], x["g"], x["beta"], _held(state, H, dk, dv),
        x["conv"], x["tail"], x["live"], x["snap"], x["fresh"], x["valid"],
        interpret=True)
    return o, _heads(held, H, dk, dv), conv


CASES = {
    # name: (heads, key dim, value dim, a decay a channel, bucket)
    "channel-128x128-bucket16": (2, 128, 128, True, 16),
    "channel-128x128-one-token": (2, 128, 128, True, 1),
    "head-96x192-bucket16": (2, 96, 192, False, 16),
    "head-96x192-one-token": (2, 96, 192, False, 1),
    "head-128x128-bucket16": (2, 128, 128, False, 16),
    "channel-16x32-three-groups": (4, 16, 32, True, 40),
    "head-24x8-bucket8": (2, 24, 8, False, 8),
}


@pytest.fixture(scope="module", params=list(CASES))
def ran(request):
    """One run of the kernel and of the oracle a case."""
    H, dk, dv, by_channel, S = CASES[request.param]
    with jax.default_matmul_precision("highest"):
        x = _inputs(H, dk, dv, by_channel, S)
        want_o, want_S = _oracle(x)
        o, state, conv = _kernel(x, H, dk, dv)
    return x, np.asarray(want_o), np.asarray(want_S), np.asarray(o), \
        np.asarray(state), np.asarray(conv.astype(jnp.float32))


def test_the_read_out_equals_the_xla_forms(ran):
    x, want_o, _, o, _, _ = ran
    real = np.asarray(jnp.arange(o.shape[1])[None, :] < x["valid"][:, None])
    assert np.max(np.abs(o - want_o)[real]) < TOL
    assert not np.any(o[~real]), "slots past a row's tokens read zero"


def test_the_live_slot_holds_the_new_state(ran):
    x, _, want_S, _, state, _ = ran
    for b in (0, 1, 4):
        assert np.max(np.abs(state[LIVE[b]] - want_S[b])) < TOL


def test_the_snapshot_slot_is_written_where_one_is_named(ran):
    """Row 0 names a snapshot slot and gets it; row 2 names one and has no
    tokens, so nothing is written there."""
    x, _, want_S, _, state, _ = ran
    assert np.max(np.abs(state[SNAP[0]] - want_S[0])) < TOL
    assert np.array_equal(state[SNAP[0]], state[LIVE[0]])
    assert np.array_equal(state[SNAP[2]], np.asarray(x["state"][SNAP[2]]))


def test_every_slot_the_pass_does_not_name_is_bit_identical(ran):
    """An idle row's slot, the slots no row holds, and whatever slot a row
    WITHOUT one (index -1, clipped to 0 where an index is formed) might
    have touched."""
    x, _, _, _, state, conv = ran
    written = {LIVE[0], LIVE[1], LIVE[4], SNAP[0]}
    before = np.asarray(x["state"])
    before_conv = np.asarray(x["conv"].astype(jnp.float32))
    for s in set(range(SLOTS)) - written:
        assert np.array_equal(state[s], before[s]), s
        assert np.array_equal(conv[s], before_conv[s]), s


def test_the_conv_tail_is_written_by_row(ran):
    x, _, _, _, _, conv = ran
    tail = np.asarray(x["tail"].astype(jnp.float32))
    for slot, b in ((LIVE[0], 0), (LIVE[1], 1), (LIVE[4], 4), (SNAP[0], 0)):
        assert np.array_equal(conv[slot].reshape(-1)[:WIDTH], tail[b])
        assert not np.any(conv[slot].reshape(-1)[WIDTH:])


def test_a_strong_decay_neither_overflows_nor_drifts():
    """Decays of exp(-40) a token and more: nothing is split into factors,
    so nothing overflows, and the kernel equals the chunk form."""
    H, dk, dv = 2, 16, 32
    x = _inputs(H, dk, dv, True, 16, seed=3, decay=40.0)
    want_o, want_S = _oracle(x)
    o, state, _ = _kernel(x, H, dk, dv)
    assert np.isfinite(np.asarray(o)).all()
    assert float(jnp.max(jnp.abs(o[0] - want_o[0]))) < TOL
    assert float(jnp.max(jnp.abs(state[LIVE[0]] - want_S[0]))) < TOL


@pytest.mark.parametrize("by_channel", [True, False], ids=["channel", "head"])
def test_two_passes_hold_float32_and_a_bfloat16_state_would_not(by_channel):
    """A bucket, then a second from what the first left: the kernel equals
    the XLA forms through both, and the same XLA forms with the state
    rounded to bfloat16 in between (what a narrower slot would hold) miss
    by more than ten times the tolerance."""
    H, dk, dv, S = 2, 16, 32, 16
    x = _inputs(H, dk, dv, by_channel, S, seed=5, decay=0.05)
    x["state"] = x["state"] * 16.0
    x["fresh"] = jnp.zeros_like(x["fresh"])
    second = dict(_inputs(H, dk, dv, by_channel, S, seed=6, decay=0.05),
                  fresh=x["fresh"])

    def through(first_pass, second_pass, between=lambda s: s):
        _, s1 = first_pass(x)
        state = x["state"].at[x["live"][0]].set(between(s1[0]))
        return second_pass(second, state)

    def kernel_state(x_, state=None):
        o, s, _ = _kernel(x_, H, dk, dv, state)
        return o, s[jnp.clip(x_["live"], 0)]

    want_o, _ = through(_oracle, _oracle)
    got_o, _ = through(kernel_state, kernel_state)
    rounded_o, _ = through(
        _oracle, _oracle,
        lambda s: s.astype(jnp.bfloat16).astype(jnp.float32))
    assert float(jnp.max(jnp.abs(got_o[0] - want_o[0]))) < TOL
    assert float(jnp.max(jnp.abs(rounded_o[0] - want_o[0]))) > 10 * TOL


def test_a_narrower_state_is_refused():
    H, dk, dv = 2, 16, 32
    x = _inputs(H, dk, dv, True, 8)
    with pytest.raises(ValueError, match="float32"):
        lsp.delta_rule_slots(
            x["q"], x["k"], x["v"], x["g"], x["beta"],
            _held(x["state"], H, dk, dv).astype(jnp.bfloat16), x["conv"],
            x["tail"], x["live"], x["snap"], x["fresh"], x["valid"],
            interpret=True)


# -- who chooses, and the form the cache is held in ------------------------------
@pytest.mark.parametrize("platform,dtype,dk,dv,heads,want", [
    ("tpu", "float32", 128, 128, 64, "pallas-state"),   # Solar-Open2
    ("tpu", "float32", 96, 192, 30, "pallas-state"),    # Olmo-Hybrid: pairs
    ("tpu", "float32", 96, 192, 15, "xla"),             # no pairs to make
    ("tpu", "float32", 96, 96, 30, "xla"),              # 192 lanes a pair
    ("tpu", "float32", 100, 128, 8, "xla"),             # rows off the tile
    ("tpu", "bfloat16", 128, 128, 64, "xla"),
    ("cpu", "float32", 128, 128, 64, "xla"),
    ("gpu", "float32", 128, 128, 64, "xla"),
])
def test_the_choice_is_a_function_of_what_it_is_given(
        platform, dtype, dk, dv, heads, want):
    assert kernels.linear_state_backend(
        platform=platform, state_dtype=dtype, key_dim=dk, value_dim=dv,
        heads=heads) == want
    assert want in kernels.STATE_BACKENDS


@pytest.mark.parametrize("preset,xla,kernel,tail", [
    ("solar-open2-250b", (64, 128, 128), (64, 128, 128), (576, 128)),
    ("olmo-hybrid-7b", (4320, 128), (15, 96, 384), (272, 128)),
    ("tiny-hybrid", (8, 128), (2, 16, 32), (16, 128)),
])
def test_the_cache_is_held_for_who_updates_it(preset, xla, kernel, tail):
    """Under XLA the parent's layout, to the shape; under the kernel a
    slot's state with nothing padded and its tail as rows of 128."""
    cfg = dataclasses.replace(
        PRESETS[preset], num_layers=len(PRESETS[preset].period_) or 4)
    la = cfg.linear_attn
    assert llama.state_slot_shape(la) == xla
    assert llama.state_slot_shape(la, "pallas-state") == kernel
    assert np.prod(xla) == np.prod(kernel) == (
        la.num_heads * la.key_head_dim * la.value_head_dim)
    width = (la.conv_kernel - 1) * la.conv_size
    n = cfg.count_mixers("linear")
    for impl, conv in (("xla", (width,)), ("pallas-state", tail)):
        cache = jax.eval_shape(lambda impl=impl: llama.make_cache(
            cfg, 8, PAGE, state_slots=5, state_impl=impl))
        assert cache["state"].shape == (n, 5) + llama.state_slot_shape(la, impl)
        assert cache["state"].dtype == jnp.float32
        assert cache["conv"].shape == (n, 5) + conv
        specs = llama.cache_specs(cfg, state_impl=impl)
        assert set(specs) == set(cache)
        assert len(specs["conv"]) == cache["conv"].ndim
        assert len(specs["state"]) == cache["state"].ndim
    assert tail[0] * 128 >= width and tail[0] % 16 == 0


# -- inside the step programs ------------------------------------------------------
def _table(rows, maxp=8):
    out = np.full((len(rows), maxp + llama.STATE_COLUMNS), -1, np.int32)
    for i, (pages, slot, snap) in enumerate(rows):
        pages = list(pages)
        out[i, :len(pages)] = pages
        out[i, maxp:] = (slot, snap)
    return jnp.asarray(out)


@pytest.mark.parametrize("preset", ["tiny-hybrid", "tiny-olmo-hybrid"])
def test_the_step_programs_equal_the_xla_path_and_snapshot_on_a_page_boundary(
        preset, monkeypatch):
    """A mixed step (a chunk row that ends on a page boundary, one that
    does not, an idle row), a second one (a decode lane, a chunk from the
    stored state) and a decode step, with the cache held for XLA and for
    the kernel: logits, every live slot and the snapshot equal; the
    snapshot slot of the row that ends off a boundary is untouched."""
    monkeypatch.setenv("OPSAGENT_PALLAS_INTERPRET", "1")
    cfg = PRESETS[preset]
    la = cfg.linear_attn
    H, dk, dv = la.num_heads, la.key_head_dim, la.value_head_dim
    p = llama.init_params(cfg, jax.random.PRNGKey(2), jnp.float32)
    p = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(
            jax.random.PRNGKey(a.size % 97), a.shape, a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, p)
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0, 500)
    table = _table([(range(4), 1, 5), (range(4, 8), 3, 6), (range(8, 12), 2, 7)])
    first = np.zeros((3, 32), np.int32)
    first[0, :32] = np.asarray(toks[0, :32])
    first[1, :20] = np.asarray(toks[1, :20])
    second = np.zeros((3, 16), np.int32)
    second[0, 0] = int(toks[0, 32])
    second[1, :7] = np.asarray(toks[1, 20:27])
    got = {}
    for impl in ("xla", "pallas-state"):
        cache = llama.make_cache(
            cfg, 16, PAGE, dtype=jnp.float32, state_slots=8, state_impl=impl)
        cache = dict(cache, state=cache["state"] + 0.25)   # snapshots' canary
        _, cache = llama.mixed_step(
            p, cfg, jnp.asarray(first), jnp.zeros((3,), jnp.int32),
            jnp.asarray([32, 20, 0]), cache, table, dtype=jnp.float32,
            kernels=Kernels(state=impl))
        after_first = cache["state"]
        mixed, cache = llama.mixed_step(
            p, cfg, jnp.asarray(second), jnp.asarray([32, 20, 0]),
            jnp.asarray([1, 7, 0]), cache, table, dtype=jnp.float32,
            kernels=Kernels(state=impl))
        decoded, cache = llama.decode_step(
            p, cfg, jnp.asarray([int(toks[0, 33]), int(toks[1, 27]), 0]),
            jnp.asarray([33, 27, 0]), cache, table,
            jnp.asarray([True, True, False]), dtype=jnp.float32,
            kernels=Kernels(state=impl))

        def slots(state):       # [layers, slots, H, dk, dv] whatever is held
            n, s = state.shape[:2]
            flat = state.reshape(n * s, *state.shape[2:])
            if impl == "pallas-state":
                return _heads(flat, H, dk, dv).reshape(n, s, H, dk, dv)
            return flat.reshape(n, s, H, dk, dv)

        got[impl] = (mixed[:2], decoded[:2], slots(after_first),
                     slots(cache["state"]))
    for a, b in zip(got["xla"], got["pallas-state"]):
        assert float(jnp.max(jnp.abs(a - b))) < (
            TOL if preset == "tiny-hybrid" else 6e-3)
    after_first = np.asarray(got["pallas-state"][2])
    assert np.array_equal(after_first[:, 5], after_first[:, 1]), \
        "32 tokens end on a page boundary: the snapshot is the live state"
    assert np.all(after_first[:, 6] == 0.25), "20 tokens do not"
    assert np.all(after_first[:, 2] == 0.25) and np.all(after_first[:, 7] == 0.25)


def test_prefill_decode_and_restore_through_the_engine_equal_the_xla_path(
        state_kernel):
    """Two turns on ``tiny-hybrid`` (a prompt, then the history re-sent with
    more, which restores the first turn's snapshot): the engine whose slots
    the kernel updates gives the XLA engine's tokens, token for token, and
    restores as many."""
    from opsagent_tpu import obs
    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    rng = np.random.default_rng(0)
    first = [int(x) for x in rng.integers(0, 500, size=90)]
    more = [int(x) for x in rng.integers(0, 500, size=30)]
    sampling = SamplingParams(max_tokens=24, temperature=0.0)
    restored = "opsagent_state_restored_tokens_total"

    def two_turns():
        eng = Engine(EngineConfig(
            model="tiny-hybrid", dtype=jnp.float32, tp=1, max_batch_size=4,
            num_pages=128, max_pages_per_seq=32, prefill_buckets=(64,),
            mixed_buckets=(16,), max_step_tokens=64, decode_block=4,
            state_snapshots=3))
        reply = eng.generate([first], sampling)[0]
        before = obs.metrics_snapshot().get(restored, 0.0)
        again = eng.generate([first + reply + more], sampling)[0]
        return (eng.impl_info(), reply, again,
                obs.metrics_snapshot()[restored] - before)

    info, *want = two_turns()
    assert info["state_impl"] == "xla" and info["state_layout"] == [3, 8, 128]
    with state_kernel():
        info, *got = two_turns()
    assert info["state_impl"] == "pallas-state"
    assert info["state_layout"] == [3, 2, 16, 32]
    assert info["state_dtype"] == "float32"
    assert got == want and got[2] == 112
