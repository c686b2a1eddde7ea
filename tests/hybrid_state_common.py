"""What the files ``tests/test_hybrid_state_*.py`` share: the model, the
reference, the fixtures and the tolerances.

A model of unlike layers (softmax attention over pages beside delta-rule
linear attention over a recurrent state, experts at one chip's share),
against the plain reference ``benchmarks/reference/solar_open2.py`` at toy
widths on seeded random weights, float32.

Tolerances, and why. Program and reference compute the same float32
mathematics in another order (chunk form against token recurrence, sorted
expert dispatch against a loop over experts), so they differ by rounding
alone: logits of magnitude ~5 agree to 2e-4 absolute (measured 4e-5 at the
worst; five times that). A recurrent state held in bfloat16 between steps
errs by 1e-2 and more after a few dozen tokens (asserted below), fifty
times the tolerance: the comparison would catch it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import solar_open2 as ref
from opsagent_tpu.models import llama
from opsagent_tpu.models.config import PRESETS

TOL = 2e-4
CFG = PRESETS["tiny-hybrid"]
PAGE = 16
MAXP = 16


@pytest.fixture(autouse=True, scope="module")
def highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def release_compiled_programs():
    """After each test of a file that imports it, drop JAX's in-process caches of
    compiled programs once the process holds more than two fifths of the
    memory mappings it may have. On the CPU every compiled program, each
    eager operation's too, holds several mappings of its own, a process
    may hold ``vm.max_map_count`` of them (65,530 here), and past that the
    next compile or the next write to the persistent compile cache dies
    with a segmentation fault or an abort: this file alone reached 59,574
    with PR 34's cases in it and died in its last test, where the parent's
    stopped some thousands short (one of its tests alone adds 28,000).
    ``jax.clear_caches()`` gives them back (30,843 -> 711 after that test);
    what is needed again is read back from the persistent cache or
    compiled again. No test here counts compiles across tests."""
    yield
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            limit = int(f.read())
        with open("/proc/self/maps") as f:
            held = sum(1 for _ in f)
    except (OSError, ValueError):
        return      # no such files: not Linux, nothing known to guard
    if held > 0.4 * limit:
        import gc

        jax.clear_caches()
        gc.collect()


def _randomised(tree, key):
    """``init_params`` leaves decay rates, offsets, biases and norms at
    zero or one; give them values, so that a dropped one shows."""
    out = {}
    for i, (name, leaf) in enumerate(sorted(tree.items())):
        k = jax.random.fold_in(key, i)
        if isinstance(leaf, dict):
            out[name] = _randomised(leaf, k)
        elif name == "a_log":
            out[name] = jnp.log(jax.random.uniform(
                k, leaf.shape, minval=1.0, maxval=16.0))
        elif name == "dt_bias":
            out[name] = jax.random.normal(k, leaf.shape) * 0.5 - 2.0
        elif name == "router_bias":
            out[name] = jax.random.normal(k, leaf.shape) * 0.3
        elif name.endswith("norm"):
            out[name] = 1 + 0.1 * jax.random.normal(k, leaf.shape)
        else:
            out[name] = leaf
    return out


@pytest.fixture(scope="module")
def params():
    return _randomised(
        llama.init_params(CFG, jax.random.PRNGKey(0), jnp.float32),
        jax.random.PRNGKey(7))


def layers_of(params, cfg=CFG):
    """(kind, float32 leaves) of every layer, in the model's order."""
    out = []
    for p in range(cfg.num_layers // len(cfg.period_)):
        for key, mixer, n in llama.period_runs(cfg):
            for j in range(n):
                out.append((
                    "gqa" if mixer == "attn" else "linear",
                    jax.tree.map(lambda a: a[p, j], params["moe_layers"][key])))
    return out


def ref_layer(x, kind, w, cfg=CFG, held=None):
    m = cfg.moe
    return ref.layer(
        x, w, kind=kind, heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
        linear_heads=cfg.linear_attn.num_heads, top_k=m.num_experts_per_token,
        scale=m.routed_scaling_factor, eps=cfg.rms_norm_eps,
        neg_eigval=cfg.linear_attn.neg_eigval,
        held=held or (m.first_expert, m.num_experts))


def ref_logits(params, tokens, cfg=CFG):
    x = params["embed"][tokens].astype(jnp.float32)
    for kind, w in layers_of(params, cfg):
        x = ref_layer(x, kind, w, cfg)
    return ref.logits(x, params["final_norm"], params["lm_head"],
                      cfg.rms_norm_eps)


def table_rows(rows):
    """rows: [(pages, state slot, snapshot slot)] -> [B, MAXP + 2]."""
    t = np.full((len(rows), MAXP + 2), -1, np.int32)
    for i, (pages, slot, snap) in enumerate(rows):
        t[i, :len(pages)] = pages
        t[i, MAXP:] = slot, snap
    return jnp.asarray(t)


def fresh_cache(slots=8):
    return llama.make_cache(CFG, 64, PAGE, dtype=jnp.float32, state_slots=slots)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 100), 0, CFG.vocab_size)


@pytest.fixture(scope="module")
def truth(params, tokens):
    return jnp.stack([ref_logits(params, tokens[i]) for i in range(2)])


# -- Olmo-Hybrid: one decay a head, dk != dv, full-rank gates, post-norm (PR 33) -
#
# Against ``benchmarks/reference/olmo_hybrid.py``. The tolerance is wider
# than the other one, and why: the Olmo2 block norms a sublayer's
# OUTPUT, so where a mixer's output is small against the values it was
# computed from (a read-out ``S^T q`` whose terms cancel) the norm scales
# float32's rounding up with it. Measured here: the float32 reference
# against itself in float64 4.5e-4, the program against the float32
# reference 1.8e-3 at the worst of 200 positions and 3e-4 at most elsewhere
# (logits of magnitude 4.4). 6e-3 is three times the worst. A state held in
# bfloat16 between steps errs more than ten times that (asserted below).
OLMO = PRESETS["tiny-olmo-hybrid"]
OLMO_TOL = 6e-3

