"""Snapshot writer: capture a fully-warmed engine as a restart artifact.

What gets captured (ISSUE 10 / ROADMAP item 4, HydraServe-style):

- **Weights in device layout**: every params-tree leaf pulled once and
  written as raw bytes in *spec-tree order* — the deterministic flatten
  of ``llama.param_specs(model_cfg)`` (quantized when the engine is).
  Restore rebuilds the same spec tree, memory-maps each file, and
  ``tree_unflatten`` reassembles the exact pytree ``shard_params``
  expects; no checkpoint parse, no host-side dtype/layout round trip
  through ``models/loader.py``.
- **The persistent XLA compile cache**: the engine's cache directory is
  copied wholesale, so a restoring engine's warmup is a cache-hit sweep
  instead of an XLA invocation per program.
- **The paged-KV allocation plan**: page geometry + cache leaf shapes —
  enough for an operator (or ``snapshot verify``) to see what the
  restore will allocate; the cache itself is rebuilt empty (KV content
  is per-request state, not artifact state).

The manifest is written last: its presence marks a complete snapshot,
so a crashed writer leaves a recognizably-partial directory instead of
a restorable-looking lie.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import time
from typing import Any

import jax
import numpy as np

from ... import obs
from ...models import llama
from ...utils.logger import get_logger
from .manifest import (
    COMPILE_CACHE_DIR,
    FORMAT_VERSION,
    MANIFEST_NAME,
    WEIGHTS_DIR,
    digest_bytes,
    fingerprint,
    write_manifest,
)

log = get_logger("snapshot")

# EngineConfig fields that determine compiled-program shapes or the
# sharded weight layout — the fingerprint's engine half. Checkpoint
# path, seed, warmup flag, host pool capacity etc. deliberately excluded:
# they change neither programs nor layout, and a snapshot must restore
# regardless of where its weights originally came from.
_ENGINE_FINGERPRINT_FIELDS = (
    "model", "tokenizer", "tp", "dp", "sp", "ep",
    "prefill_batch",
    "page_size", "num_pages", "max_pages_per_seq", "max_batch_size",
    "decode_block", "pipeline_depth", "prefill_buckets",
    "mixed_batching", "max_step_tokens", "mixed_buckets", "async_depth",
    "prefix_cache", "offload", "offload_copy_pages",
    "quantize", "kv_quantize",
)


def model_config_dict(model_cfg: Any) -> dict[str, Any]:
    """ModelConfig -> JSON-safe dict (nested MoE/MLA/rope-scaling
    dataclasses included). The engine snapshots its POST-pin model_cfg
    (MoE grouped_dispatch_min_tokens=0), so restoring it through
    Engine.__init__'s pin is idempotent and the fingerprint is stable."""
    return dataclasses.asdict(model_cfg)


def engine_config_dict(cfg: Any) -> dict[str, Any]:
    out: dict[str, Any] = {}
    for name in _ENGINE_FINGERPRINT_FIELDS:
        v = getattr(cfg, name)
        out[name] = list(v) if isinstance(v, tuple) else v
    out["dtype"] = np.dtype(cfg.dtype).name
    return out


def _spec_tree(engine: Any) -> Any:
    specs = llama.param_specs(engine.model_cfg)
    if engine.cfg.quantize:
        from ...models.quant import quantize_specs

        specs = quantize_specs(specs, mode=engine.cfg.quantize)
    return specs


def spec_leaf_paths(model_cfg: Any, quantize: str) -> list[str]:
    """Keystr per spec-tree leaf, in flatten order — the leaf-file
    naming/ordering contract shared by writer and restore."""
    from jax.sharding import PartitionSpec

    specs = llama.param_specs(model_cfg)
    if quantize:
        from ...models.quant import quantize_specs

        specs = quantize_specs(specs, mode=quantize)
    paths, _ = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, PartitionSpec)
    )
    return [jax.tree_util.keystr(kp) for kp, _ in paths]


def write_snapshot(engine: Any, path: str) -> dict[str, Any]:
    """Write ``engine``'s restart snapshot under ``path`` (created if
    needed). Returns the manifest dict. The engine keeps serving — only
    immutable state (params, compile cache, config) is read."""
    from jax.sharding import PartitionSpec

    t0 = time.perf_counter()
    os.makedirs(path, exist_ok=True)
    weights_dir = os.path.join(path, WEIGHTS_DIR)
    os.makedirs(weights_dir, exist_ok=True)

    # Leaf order contract: the spec tree's flatten (PartitionSpec leaves)
    # and the params tree's flatten walk the same structure, so index i
    # of one is index i of the other. Restore re-derives the spec tree
    # from configs alone and unflattens the leaf files through it.
    spec_leaves, _ = jax.tree_util.tree_flatten(
        _spec_tree(engine), is_leaf=lambda x: isinstance(x, PartitionSpec)
    )
    param_paths = jax.tree_util.tree_flatten_with_path(engine.params)[0]
    if len(param_paths) != len(spec_leaves):
        raise RuntimeError(
            f"params tree has {len(param_paths)} leaves but the spec "
            f"tree has {len(spec_leaves)} — param_specs drifted from "
            "the params structure; snapshot would not restore"
        )

    leaves = []
    weights_bytes = 0
    for i, (kp, leaf) in enumerate(param_paths):
        arr = np.asarray(jax.device_get(leaf))
        fname = os.path.join(WEIGHTS_DIR, f"leaf-{i:05d}.bin")
        data = arr.tobytes()
        with open(os.path.join(path, fname), "wb") as f:
            f.write(data)
        weights_bytes += len(data)
        leaves.append({
            "path": jax.tree_util.keystr(kp),
            "file": fname,
            "dtype": arr.dtype.name,
            "shape": list(arr.shape),
            "nbytes": len(data),
            "digest": digest_bytes(data),
        })

    # Persistent XLA compile cache (engine.compile_cache_dir()) -> build
    # artifact. Entries land there at COMPILE time, so `snapshot create`
    # warms the engine under OPSAGENT_COMPILE_CACHE_MIN_S=0 before
    # calling this.
    cache_entries = 0
    cache_bytes = 0
    src_cache = getattr(engine, "compile_cache_dir", None)
    dst_cache = os.path.join(path, COMPILE_CACHE_DIR)
    os.makedirs(dst_cache, exist_ok=True)
    if src_cache and os.path.isdir(src_cache):
        shutil.copytree(src_cache, dst_cache, dirs_exist_ok=True)
        for root, _dirs, files in os.walk(dst_cache):
            for f in files:
                cache_entries += 1
                cache_bytes += os.path.getsize(os.path.join(root, f))
    else:
        log.warning(
            "engine has no active compile cache dir: snapshot carries "
            "weights only (restore will recompile; "
            "jax_enable_compilation_cache is off)"
        )

    cfg = engine.cfg
    # The plan records pages in the format they have off the device
    # ([L, N, P, K, D]), whichever form this engine holds them in.
    cache_leaves = jax.tree_util.tree_flatten_with_path(engine.cache_wire)[0]
    kv_plan = {
        "num_pages": cfg.num_pages,
        "page_size": cfg.page_size,
        "max_pages_per_seq": cfg.max_pages_per_seq,
        "kv_quantize": cfg.kv_quantize,
        "leaves": [
            {
                "path": jax.tree_util.keystr(kp),
                "dtype": np.dtype(leaf.dtype).name,
                "shape": list(leaf.shape),
            }
            for kp, leaf in cache_leaves
        ],
    }

    model = model_config_dict(engine.model_cfg)
    eng_dict = engine_config_dict(cfg)
    man = {
        "format": FORMAT_VERSION,
        "created_unix": time.time(),
        "fingerprint": fingerprint(model, eng_dict),
        "model": model,
        "engine": eng_dict,
        "leaves": leaves,
        "kv_plan": kv_plan,
        "compile_cache": {"entries": cache_entries, "bytes": cache_bytes},
        "jax": {
            "version": jax.__version__,
            "backend": jax.default_backend(),
            "n_devices": len(jax.devices()),
            "mesh": {k: int(v) for k, v in dict(engine.mesh.shape).items()},
        },
    }
    write_manifest(path, man)

    dt = time.perf_counter() - t0
    obs.SNAPSHOT_OPS.inc(op="write")
    obs.SNAPSHOT_WRITE_SECONDS.observe(dt)
    obs.SNAPSHOT_BYTES.set(float(weights_bytes), part="weights")
    obs.SNAPSHOT_BYTES.set(float(cache_bytes), part="compile_cache")
    obs.flight.record(
        "snapshot_write", path=path, seconds=round(dt, 3),
        leaves=len(leaves), weights_bytes=weights_bytes,
        compile_cache_entries=cache_entries,
        fingerprint=man["fingerprint"],
    )
    log.info(
        "snapshot written to %s: %d weight leaves (%.1f MiB), %d "
        "compile-cache entries (%.1f MiB) in %.1f s [fp=%s]",
        path, len(leaves), weights_bytes / 2**20, cache_entries,
        cache_bytes / 2**20, dt, man["fingerprint"],
    )
    return man
