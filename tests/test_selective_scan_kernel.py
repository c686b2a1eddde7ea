"""The scan kernel (``ops/selective_scan_pallas.py``) in interpret mode on the
CPU against ``selective_scan`` / ``selective_scan_step`` from the same slots.

The kernel runs the recurrence as the oracle writes it, a token at a time in
float32 with the same operations in the same order, so at these sizes the
two agree to the bit or to a rounding of the exponential; the tolerance is
``tests/test_jamba.py``'s all the same, where the model's logits are
compared.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opsagent_tpu.models import llama
from opsagent_tpu.models.config import PRESETS
from opsagent_tpu.ops import kernels
from opsagent_tpu.ops.kernels import Kernels
from opsagent_tpu.ops import selective_scan_pallas as ssp
from opsagent_tpu.ops.linear_state_pallas import conv_slot_shape
from opsagent_tpu.ops.selective_scan import selective_scan

TOL = 2e-4
PAGE = 16
CFG = PRESETS["tiny-jamba"]


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
def ssm_kernel():
    """The tests' handle on the scan kernel off the chip, as
    ``test_linear_state_kernel.py::state_kernel`` is the state kernel's: an
    engine built and run inside ``with ssm_kernel():`` holds its slots for
    the kernel and runs it interpreted. Nothing in the program can name it."""
    import contextlib

    @contextlib.contextmanager
    def under():
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(
                kernels, "ssm_state_backend", lambda **_: "pallas-ssm")
            mp.setenv("OPSAGENT_PALLAS_INTERPRET", "1")
            yield

    return under


# -- the kernel against the XLA scan, slot for slot ------------------------------
# A batch of five rows: a whole bucket from a held state (and a snapshot), a
# decode lane, an idle row, a short fresh row without a slot, a whole bucket
# from a fresh slot.
LIVE = (3, 7, 5, -1, 9)
SNAP = (10, -1, 11, -1, -1)
FRESH = (False, False, False, True, True)
SLOTS = 12


def _inputs(S, C, N=16, seed=0):
    B = len(LIVE)
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    W = 3 * C
    return dict(
        x=f(B, S, C), dt=jax.nn.softplus(f(B, S, C) - 1.0),
        A=-jnp.exp(f(N, C)), Bm=f(B, S, N), Cm=f(B, S, N),
        state=f(SLOTS, N, C),
        conv=jnp.asarray(rng.standard_normal(
            (SLOTS, *conv_slot_shape(W))), jnp.bfloat16),
        tail=jnp.asarray(rng.standard_normal((B, W)), jnp.bfloat16),
        live=jnp.asarray(LIVE, jnp.int32), snap=jnp.asarray(SNAP, jnp.int32),
        fresh=jnp.asarray(FRESH),
        valid=jnp.asarray([S, 1, 0, min(S, 3), S], jnp.int32),
    )


def _against_the_oracle(a, S, C):
    y, state, conv = ssp.selective_scan_slots(**a, interpret=True)
    h0 = jnp.where(a["fresh"][:, None, None], 0.0,
                   a["state"][jnp.clip(a["live"], 0)])
    want, h1 = selective_scan(
        a["x"], a["dt"], a["A"], a["Bm"], a["Cm"], h0, a["valid"])
    W = 3 * C
    for b, (live, snap) in enumerate(zip(LIVE, SNAP)):
        v = int(a["valid"][b])
        assert float(jnp.max(jnp.abs(
            y[b, :v] - want[b, :v]), initial=0.0)) < 1e-5
        for slot in (live, snap) if v else ():
            if slot >= 0:
                assert float(jnp.max(jnp.abs(state[slot] - h1[b]))) < 1e-5
                np.testing.assert_array_equal(
                    conv[slot].reshape(-1)[:W], a["tail"][b])
    # an idle row's slot, a slot no row holds: nothing was written
    for slot in (5, 11, 0, 1):
        np.testing.assert_array_equal(state[slot], a["state"][slot])
        np.testing.assert_array_equal(conv[slot], a["conv"][slot])


@pytest.mark.parametrize("S,C", [
    (1, 128), (5, 256), (16, 2048), (40, 384), (16, 5120)],
    ids=["decode", "short", "two-chunks", "odd-lanes", "the-cells-width"])
def test_the_kernel_is_the_xla_scan_from_the_same_slots(S, C):
    """Through a chunk row, a decode lane, an idle row, a row without a
    slot and a fresh row: outputs, live and snapshot slots and conv tails
    are the oracle's, and no other slot is touched. ``the-cells-width`` is
    the benchmark's ``[16, 5120]`` state at its bucket of 16."""
    _against_the_oracle(_inputs(S, C), S, C)


def test_a_row_split_over_channel_blocks_is_the_same(monkeypatch):
    """With the token tiles' budget cut, a row's channels go through the
    ring in four blocks, reads ahead and writes behind included."""
    monkeypatch.setattr(ssp, "BLOCK_BYTES", 3 * 16 * 128 * 4)
    assert ssp.block_channels(512, 16) == 128
    _against_the_oracle(_inputs(16, 512, seed=2), 16, 512)


def test_the_blocks_of_the_cells_shapes():
    """The cell's programs: a mixed bucket of 16 and a decode pass take a
    row's 5120 channels whole; the prefill bucket of 256 in four blocks."""
    assert ssp.block_channels(5120, 16) == 5120
    assert ssp.block_channels(5120, 8) == 5120
    assert ssp.block_channels(5120, 256) == 1280
    assert kernels.ssm_state_backend(
        platform="tpu", state_dtype="float32", d_state=16, d_inner=5120
    ) == "pallas-ssm"   # (each reason it is not: tests/test_kernels.py)


# -- inside the model --------------------------------------------------------------
def _table(rows, maxp=8):
    t = np.full((len(rows), maxp + 2), -1, np.int32)
    for i, (pages, slot, snap) in enumerate(rows):
        t[i, :len(pages)] = pages
        t[i, maxp:] = slot, snap
    return jnp.asarray(t)


def test_a_mixed_step_under_the_kernel_is_the_step_under_xla(monkeypatch):
    """Two mixed steps (chunks, then a decode lane beside a chunk that ends
    on a page boundary with a snapshot armed) with the slots held for the
    kernel and for XLA: logits, states and conv tails agree."""
    monkeypatch.setenv("OPSAGENT_PALLAS_INTERPRET", "1")
    params = llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(1)
    first = jnp.asarray(rng.integers(1, 500, (3, 16)), jnp.int32)
    second = jnp.asarray(rng.integers(1, 500, (3, 16)), jnp.int32)
    table = _table([(range(4), 1, 6), (range(4, 8), 3, 7), (range(8, 12), 5, -1)])
    got = {}
    for impl in ("xla", "pallas-ssm"):
        cache = llama.make_cache(
            CFG, 32, PAGE, jnp.float32, state_slots=8, state_impl=impl)
        assert cache["conv"].ndim == (4 if impl == "pallas-ssm" else 3)
        _, cache = llama.mixed_step(
            params, CFG, first, jnp.zeros((3,), jnp.int32),
            jnp.asarray([16, 9, 0]), cache, table, dtype=jnp.float32,
            kernels=Kernels(state=impl))
        logits, cache = llama.mixed_step(
            params, CFG, second, jnp.asarray([16, 9, 0]),
            jnp.asarray([1, 7, 0]), cache, table, dtype=jnp.float32,
            kernels=Kernels(state=impl))
        got[impl] = (logits[:2], cache)
    (want, a), (have, b) = got["xla"], got["pallas-ssm"]
    assert float(jnp.max(jnp.abs(want - have))) < TOL
    assert float(jnp.max(jnp.abs(a["state"] - b["state"]))) < TOL
    assert float(jnp.max(jnp.abs(b["state"][:, 7]))) > 0, "a snapshot at 16"
    W = 3 * CFG.mamba.d_inner
    flat = b["conv"].reshape(*b["conv"].shape[:2], -1)[..., :W]
    assert float(jnp.max(jnp.abs(a["conv"] - flat))) < TOL
    for k in ("k", "v"):
        assert float(jnp.max(jnp.abs(a[k] - b[k]))) < TOL


def test_two_turns_through_an_engine_that_holds_its_slots_for_the_kernel(
        ssm_kernel):
    """The engine's normal path with the kernel in the code's choice: a
    turn, then the history re-sent after a restored snapshot; greedy tokens
    are those of an engine that runs the XLA scan."""
    from opsagent_tpu import obs
    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    shape = dict(
        model="tiny-jamba", dtype=jnp.float32, tp=1, max_batch_size=4,
        num_pages=128, max_pages_per_seq=32, prefill_buckets=(64,),
        mixed_buckets=(16,), max_step_tokens=64, decode_block=4,
        state_snapshots=3)
    rng = np.random.default_rng(0)
    first = [int(x) for x in rng.integers(0, 500, size=90)]
    more = [int(x) for x in rng.integers(0, 500, size=30)]
    sampling = SamplingParams(max_tokens=24, temperature=0.0)
    steps = 'opsagent_ssm_scan_steps_total{kind="%s"}'

    def turns(eng):
        reply = eng.generate([first], sampling)[0]
        return reply, eng.generate([first + reply + more], sampling)[0]

    want = turns(Engine(EngineConfig(**shape)))
    with ssm_kernel():
        eng = Engine(EngineConfig(**shape))
        info = eng.impl_info()
        assert info["state_impl"] == "pallas-ssm"
        assert info["state_layout"] == [6, 16, 128]
        assert eng.cache["conv"].shape[2:] == conv_slot_shape(3 * 128)
        before = obs.metrics_snapshot()
        assert turns(eng) == want
    snap = obs.metrics_snapshot()
    assert snap["opsagent_state_restored_tokens_total"] - before[
        "opsagent_state_restored_tokens_total"] == 112
    # under the kernel a pass computes the steps that carry a token: the
    # first prompt and 23 reply tokens fed back, then the 144 - 112 tokens
    # past the restored snapshot and 23 more
    for kind in ("real", "computed"):
        assert snap[steps % kind] - before[steps % kind] == (
            (90 + 23) + (32 + 23)) * 6
