"""Time the page write's scatter alone on the chip: what does a row cost?

``chiprun -- python scripts/kv_write_microbench.py`` (PERF.md section 6,
PR 39, step 0). One process, one chip. A donated, layer-stacked cache at a
benchmark cell's shape (bfloat16, merged rows ``[layers * pages * 16,
K * D]``) takes ``CALLS`` scatters under one ``jit``, each into another
layer, so that a call's fixed cost shows beside what a row costs. Each
form is timed handed 1024, 512, 256 and 128 rows, with 27 real indices and
the rest past the end (a tick of cell 1) and with every index real:

- ``scatter``: ``pf.at[flat].set(rows, mode="drop")``, the indices given.
- ``scatter_unique``: the same with distinct past-the-end indices and
  ``unique_indices=True`` (does the chip's scatter walk its indices one
  after another because they may collide?).
- ``write_pages``: ``ops.attention.write_pages`` as a rows program calls
  it (``[B, S]`` slots: the index arithmetic and its ``take_along_axis``
  inside every call).
- ``write_kv_tokens``: the packed form's entry, one side (``[T]`` slots
  computed once, a layer's offset added a call).

The time is the host clock around ``block_until_ready`` over ``REPS`` runs
after one that compiles, per call. Lines go to stdout and to
``chiprun_out/kv_write_microbench.jsonl``. On the CPU it refuses to run: a
time from there would mean nothing (``--rehearse`` walks the same control
flow there at toy shapes, checks each form's cache against a numpy scatter,
prints no time and exits 3).
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from opsagent_tpu.ops import attention  # noqa: E402

CALLS, REPS, PAGE = 28, 5, 16

# name: layers, pages a layer, K, D (the cells' caches: PERF.md section 4)
SHAPES = {
    "cell1": (28, 2560, 4, 128),
    "cell2": (8, 2048, 8, 128),
    "cell4": (8, 2048, 30, 128),
}
# rows handed to the scatter, as (B, S) of the rows program that hands them
ROWS = {1024: (32, 32), 512: (32, 16), 256: (16, 16), 128: (8, 16)}
REAL = 27

REHEARSE = "--rehearse" in sys.argv


def make_tick(rng, B, S, N, real):
    """Page table, start and q_lens of rows that carry ``real`` tokens in
    all (None: every slot): decode rows of one token and, where tokens are
    left, chunk rows; each row on pages of its own."""
    q_lens = np.zeros(B, np.int32)
    if real is None:
        q_lens[:] = S
    else:
        chunk = max(real - (B - 1), 0)          # one chunk row takes the rest
        q_lens[0] = min(chunk, S)
        left = real - q_lens[0]
        q_lens[1:1 + left] = 1
    maxp = 8
    pages = rng.permutation(N)[: B * maxp].reshape(B, maxp).astype(np.int32)
    start = rng.integers(0, (maxp - 3) * PAGE, size=B).astype(np.int32)
    return pages, start, q_lens


def slots_of(pages, start, q_lens, S):
    """[B, S] a layer's slot of every row slot, -1 where padded (numpy)."""
    pos = start[:, None] + np.arange(S)[None, :]
    flat = np.take_along_axis(pages, pos // PAGE, axis=1) * PAGE + pos % PAGE
    return np.where(np.arange(S)[None, :] < q_lens[:, None], flat, -1)


def expected(new, slots, layers: int, per_layer: int) -> np.ndarray:
    """The cache ``CALLS`` writes of ``new`` leave, from zeros, by numpy."""
    width = new.shape[-2] * new.shape[-1]
    want = np.zeros((layers * per_layer, width), np.float32)
    live = slots >= 0
    for call in range(CALLS):
        rows = np.asarray(new * jnp.asarray(1 + call, new.dtype), np.float32)
        at = (call % layers) * per_layer + slots[live]
        want[at] = rows.reshape(-1, width)[live]
    return want


def over_calls(op, layers: int):
    """``op(pf, new, layer, *args)`` ``CALLS`` times under one jit, the
    cache its carry and donated, each call's rows another multiple of
    ``new``."""
    def run(pf, new, *args):
        def body(pf, call):
            scaled = (new * (1 + call).astype(new.dtype)).astype(new.dtype)
            return op(pf, scaled, call % layers, *args), None
        pf, _ = jax.lax.scan(body, pf, jnp.arange(CALLS))
        return pf
    return jax.jit(run, donate_argnums=0)


def timed(fn, pf, args):
    pf = jax.block_until_ready(fn(pf, *args))
    if REHEARSE:
        return None, pf
    t0 = time.perf_counter()
    for _ in range(REPS):
        pf = fn(pf, *args)
    jax.block_until_ready(pf)
    return (time.perf_counter() - t0) / REPS / CALLS * 1e6, pf


def main() -> int:
    dev = jax.devices()[0]
    rows_tried = dict(ROWS)
    if REHEARSE:
        SHAPES.clear()
        SHAPES["toy"] = (3, 40, 2, 16)
        rows_tried = {32: (4, 8), 16: (2, 8)}
    elif dev.platform != "tpu":
        print(f"kv_write_microbench: needs a TPU, found {dev.platform}")
        return 1
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/kv_write_microbench.jsonl", "w")
    names = [a for a in sys.argv[1:] if a != "--rehearse"] or list(SHAPES)
    for name in names:
        L, N, K, D = SHAPES[name]
        per_layer, width = N * PAGE, K * D
        oob = L * per_layer
        rng = np.random.default_rng(39)
        pf = jnp.zeros((L, N, PAGE, width), jnp.bfloat16)
        for R, (B, S) in rows_tried.items():
            new = jnp.asarray(
                rng.standard_normal((B, S, K, D), np.float32), jnp.bfloat16)
            for real in (min(REAL, R), None):
                pages, start, q_lens = make_tick(rng, B, S, N, real)
                slots = slots_of(pages, start, q_lens, S).reshape(-1)
                # the past-the-end rows of ``scatter_unique`` differ
                distinct = np.where(slots >= 0, slots, per_layer + np.arange(R))
                tok = np.argsort(slots < 0, kind="stable")   # real ones first
                packed = jnp.asarray(np.asarray(new).reshape(R, K, D)[tok])[None]

                def scatter(pf, rows, layer, flat, unique=False):
                    idx = jnp.where(
                        flat < per_layer, flat + layer * per_layer,
                        oob + flat)
                    flatpf = pf.reshape(oob, width)
                    return flatpf.at[idx].set(
                        rows.reshape(R, width), mode="drop",
                        unique_indices=unique).reshape(pf.shape)

                forms = {
                    "scatter": (
                        scatter,
                        (jnp.asarray(np.where(slots >= 0, slots, per_layer)),)),
                    "scatter_unique": (
                        lambda *a: scatter(*a, unique=True),
                        (jnp.asarray(distinct),)),
                    "write_pages": (
                        lambda pf, rows, layer, t, st, ql: (
                            attention.write_pages(
                                pf, rows, t, st, valid_len=ql, layer=layer)),
                        (jnp.asarray(pages), jnp.asarray(start),
                         jnp.asarray(q_lens))),
                    "write_kv_tokens": (
                        lambda pf, rows, layer, sl: attention.write_kv_tokens(
                            pf, pf, rows, rows, sl, layer)[0],
                        (jnp.asarray(slots[tok]),)),
                }
                line = {
                    "shape": name, "device": dev.device_kind,
                    "cache": [oob, width], "rows": R, "row_bytes": width * 2,
                    "real": int((slots >= 0).sum()), "calls": CALLS,
                    "us_per_call": {},
                }
                for form, (op, args) in forms.items():
                    rows = packed if form == "write_kv_tokens" else new
                    if REHEARSE:
                        pf = jnp.zeros_like(pf)
                    us, pf = timed(
                        over_calls(op, L), pf, (rows, *args))
                    line["us_per_call"][form] = us and round(us, 2)
                    if REHEARSE:    # every form leaves numpy's cache
                        got = np.asarray(pf, np.float32).reshape(oob, width)
                        assert np.array_equal(
                            got, expected(new, slots, L, per_layer)), form
                print(json.dumps(line), flush=True)
                sink.write(json.dumps(line) + "\n")
                sink.flush()
        del pf
    return 3 if REHEARSE else 0


if __name__ == "__main__":
    sys.exit(main())
