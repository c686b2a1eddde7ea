"""Time the readers of paged keys and values against each other on the chip.

``chiprun -- python scripts/attn_microbench.py`` (PERF.md section 6, PR 29).
One process, one chip. Each candidate runs the same rows at a benchmark
cell's shapes: a seeded page table, lengths drawn from the cell's range,
most rows decoding (``q_len`` 1) and a few carrying a chunk, through
``LAYERS`` layers of a layer-stacked cache under one ``jit``; the time is
the host clock around ``block_until_ready`` over ``REPS`` calls after one
that compiles, per layer. Every candidate's output is compared with the
gather's (the last layer's) on the live query slots. Lines go to stdout and to
``chiprun_out/attn_microbench.jsonl``. On the CPU it refuses to run: a
time from there would mean nothing (``--rehearse`` walks the same control
flow there at toy shapes with the kernels interpreted, prints no time and
exits 3).
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
from opsagent_tpu.ops.paged_attention_stream import (  # noqa: E402
    paged_ragged_attention_stream,
)

LAYERS, REPS, PAGE = 4, 5, 16

# name: B, H, K, D, pages a layer, MaxP, (min, max) cached tokens, chunk
# rows, the query widths S and the key-block pages to try
SHAPES = {
    "cell1": (32, 28, 4, 128, 2560, 384, (2048, 3900), 6, (1, 16, 32),
              (32, 64)),
    "cell2": (16, 64, 8, 128, 2048, 104, (512, 1600), 3, (1, 64), (32, 64)),
    "cell3": (32, 64, 8, 128, 12288, 512, (2048, 7000), 4, (1, 16), (32, 64)),
    "cell4": (16, 30, 30, 128, 2048, 336, (1024, 5000), 2, (1, 16, 32),
              (32,)),
    # MLA's absorbed queries over the latent pages, which keys and values
    # share (one array handed twice, as ``llama.mixed_step`` hands it)
    "cell5": (16, 20, 1, 640, 16384, 1216, (5000, 18000), 2, (1, 16),
              (16, 32)),
}
SHARED = {"cell5", "toy-latent"}   # values are the keys' own pages


def make_rows(rng, B, S, N, MaxP, span, chunk_rows):
    """Page table, start and q_lens of one step: ``chunk_rows`` rows carry
    ``S`` new tokens, the others one."""
    total = rng.integers(span[0], span[1], size=B)
    q_lens = np.ones(B, np.int32)
    q_lens[: min(chunk_rows, B) if S > 1 else 0] = S
    total = np.minimum(total, MaxP * PAGE)
    # The pool may be smaller than B full rows (cell 1: 2560 pages for 32
    # rows of up to 244): rows share pages, as rows share a prefix.
    table = np.full((B, MaxP), -1, np.int32)
    for b in range(B):
        need = -(-int(total[b]) // PAGE)
        table[b, :need] = rng.permutation(N)[:need]
    return table, (total - q_lens).astype(np.int32), q_lens


REHEARSE = "--rehearse" in sys.argv


def timed(fn, args):
    out = jax.block_until_ready(fn(*args))
    if REHEARSE:
        return None, out
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / REPS / LAYERS * 1e3, out


def over_layers(op, shared=False):
    """``op(q, kc, vc, table, start, q_lens, layer)`` through LAYERS layers
    of one stacked cache, each layer's output feeding the next query.
    ``shared``: the values are the keys' pages, one array handed twice (a
    ``jit`` would make two of it), so a reader can tell."""
    def run(q, kc, vc, table, start, q_lens):
        if shared:
            vc = kc

        def body(x, layer):
            out = op(x, kc, vc, table, start, q_lens, layer)
            return (q + out * 0.01).astype(q.dtype), out
        _, outs = jax.lax.scan(body, q, jnp.arange(LAYERS))
        return outs[-1]
    return jax.jit(run)


def upstream_rpa(q, kc, vc, table, start, q_lens):
    """JAX's own ragged paged attention over the same rows: its contract is
    packed tokens ``[T, H, D]`` and one page array with K and V heads
    interleaved, so both are built here, outside the timing."""
    from jax.experimental.pallas.ops.tpu.ragged_paged_attention import (
        ragged_paged_attention,
    )

    B, S, H, D = q.shape
    K = kc.shape[-1] // D
    ql = np.asarray(q_lens)
    cu = np.concatenate([[0], np.cumsum(ql)]).astype(np.int32)
    T = int(-(-cu[-1] // 128) * 128)
    rows = np.concatenate([b * S + np.arange(n) for b, n in enumerate(ql)])
    packed = jnp.zeros((T, H, D), q.dtype).at[: len(rows)].set(
        q.reshape(B * S, H, D)[rows]
    )
    Lr, N = kc.shape[:2]
    kv = jnp.stack(
        [kc.reshape(Lr, N, PAGE, K, D), vc.reshape(Lr, N, PAGE, K, D)], axis=4
    ).reshape(Lr, N, PAGE, 2 * K, D)
    args = (
        jnp.asarray(np.asarray(start) + ql, jnp.int32),
        jnp.maximum(jnp.asarray(table), 0), jnp.asarray(cu),
        jnp.asarray([B], jnp.int32),
    )

    @jax.jit
    def run(packed, kv, kv_lens, pages, cu, n):
        def body(x, layer):
            out = ragged_paged_attention(
                x, kv[layer], kv_lens, pages, cu, n, sm_scale=D ** -0.5
            )
            return (packed + out * 0.01).astype(packed.dtype), None
        x, _ = jax.lax.scan(body, packed, jnp.arange(Lr))
        return x

    ms, _ = timed(run, (packed, kv, *args))
    return ms


def main() -> int:
    dev = jax.devices()[0]
    if REHEARSE:
        SHAPES.clear()
        SHAPES["toy"] = (3, 8, 4, 128, 40, 12, (60, 150), 1, (1, 16), (2, 4))
        SHAPES["toy-latent"] = (
            3, 20, 1, 128, 40, 12, (60, 150), 1, (1, 16), (2,))
    elif dev.platform != "tpu":
        print(f"attn_microbench: needs a TPU, found {dev.platform}")
        return 1
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open("chiprun_out/attn_microbench.jsonl", "w")
    names = [a for a in sys.argv[1:] if a != "--rehearse"] or list(SHAPES)
    for name in names:
        B, H, K, D, N, MaxP, span, chunk_rows, widths, blocks = SHAPES[name]
        rng = np.random.default_rng(29)
        kc = jnp.asarray(
            rng.standard_normal((LAYERS, N, PAGE, K * D), np.float32),
            jnp.bfloat16,
        )
        vc = kc if name in SHARED else jnp.asarray(
            rng.standard_normal((LAYERS, N, PAGE, K * D), np.float32),
            jnp.bfloat16,
        )
        for S in widths:
            table, start, q_lens = make_rows(
                rng, B, S, N, MaxP, span, chunk_rows
            )
            q = jnp.asarray(
                rng.standard_normal((B, S, H, D), np.float32), jnp.bfloat16
            )
            args = (q, kc, vc, jnp.asarray(table), jnp.asarray(start),
                    jnp.asarray(q_lens))
            live = np.arange(S)[None, :] < q_lens[:, None]
            line = {
                "shape": name, "device": dev.device_kind, "B": B, "S": S,
                "heads": f"{H}:{K}", "max_pages": MaxP,
                "live_pages_share": round(float(
                    np.ceil((start + q_lens) / PAGE).sum() / (B * MaxP)), 3),
                "ms_per_layer": {}, "max_err_vs_gather": {},
            }
            shared = name in SHARED
            ms, ref = timed(over_layers(
                lambda x, k_, v_, t, st, ql, ly: (
                    attention.paged_ragged_attention(
                        x, k_, v_, t, st, ql, layer=ly)),
                shared,
            ), args)
            line["ms_per_layer"]["gather"] = ms and round(ms, 4)
            ref = np.asarray(ref, np.float32)
            candidates = {
                f"stream_kb{bp * PAGE}": (
                    lambda x, k_, v_, t, st, ql, ly, bp=bp: (
                        paged_ragged_attention_stream(
                            x, k_, v_, t, st, ql, layer=ly, block_pages=bp,
                            interpret=REHEARSE))
                )
                for bp in blocks
            }
            for cand, op in candidates.items():
                try:
                    ms, got = timed(over_layers(op, shared), args)
                except Exception as e:  # noqa: BLE001 - a refusal is a finding
                    line["ms_per_layer"][cand] = f"refused: {str(e)[:200]}"
                    continue
                got = np.asarray(got, np.float32)
                line["ms_per_layer"][cand] = ms and round(ms, 4)
                line["max_err_vs_gather"][cand] = float(
                    np.abs(got - ref)[live].max()
                )
            if name == "cell1" and S in (16, 32) and not REHEARSE:
                try:
                    line["ms_per_layer"]["jax_ragged_paged_attention"] = round(
                        upstream_rpa(*args), 4)
                except Exception as e:  # noqa: BLE001
                    line["ms_per_layer"]["jax_ragged_paged_attention"] = (
                        f"refused: {str(e)[:200]}")
            print(json.dumps(line), flush=True)
            sink.write(json.dumps(line) + "\n")
            sink.flush()
    return 3 if REHEARSE else 0


if __name__ == "__main__":
    sys.exit(main())
