"""``opsagent audit-fanout`` — run one cluster-scale audit fan-out.

Builds an in-process fleet (N replicas of one engine config behind a
FleetRouter with the fleet-global KV directory on), generates the seeded
synthetic cluster, and runs the plan/scatter/reduce pipeline over it:
the CLI form of the ``audit-fanout`` bench stage, for poking at fan-out
behavior (prefix-hit rate, shed/retry containment, reduce determinism)
without the bench harness around it.

Prints the deterministic report to stdout (``--json`` for the canonical
byte form the tests compare) and the run's serving-side stats to stderr.
Exit 0 when every child audited ok, 1 when any child degraded to a
``finding_unavailable`` row.
"""

from __future__ import annotations

import json
import sys


def run_audit_fanout(
    model: str = "tiny-test",
    resources: int = 64,
    seed: int = 0,
    issue_fraction: float = 0.25,
    replicas: int = 2,
    max_inflight: int = 8,
    max_tokens: int = 16,
    flight_sample: int = 0,
    as_json: bool = False,
    out: str = "",
) -> int:
    """CLI body (jax imports deferred so ``--help`` stays instant)."""
    from dataclasses import replace as dc_replace

    import jax
    import jax.numpy as jnp

    from ..agent.fanout import FanoutConfig, SynthCluster, run_audit
    from ..serving.api import ServingStack
    from ..serving.engine import Engine, EngineConfig
    from ..serving.fleet.router import FleetRouter

    # The replicas are N engines in THIS process — the one process that
    # may hold the chip — and each is sized below as if it had the device
    # to itself: what they run on, at what dtype, and how many share it
    # is part of the stats line, and the device's allocator refuses the
    # sum if it does not fit.
    dev = jax.devices()[0]
    dtype = jnp.bfloat16 if dev.platform == "tpu" else jnp.float32
    cfg = EngineConfig(
        model=model,
        dtype=dtype,
        max_batch_size=8,
        page_size=16,
        num_pages=2048,
        max_pages_per_seq=64,
        prefill_buckets=(64, 128, 256),
        decode_block=8,
        offload=True,
    )
    router = FleetRouter(sticky=False)
    stacks = []
    try:
        for i in range(max(1, replicas)):
            stack = ServingStack(Engine(dc_replace(cfg)))
            stacks.append(stack)
            stack.engine.warmup("sessions")
            router.add_local(stack, f"fanout-r{i}")
        cluster = SynthCluster(
            resources=resources, seed=seed, issue_fraction=issue_fraction,
        )
        rep = run_audit(router, cluster, FanoutConfig(
            max_inflight=max_inflight,
            max_tokens=max_tokens,
            flight_sample=flight_sample,
        ))
    finally:
        for stack in stacks:
            stack.close()
    if out:
        with open(out, "w") as f:
            f.write(rep.canonical + "\n")
    if as_json:
        print(rep.canonical)
    else:
        print(json.dumps(rep.report, indent=2))
    stats = dict(rep.stats)
    stats["recall"] = rep.recall(cluster)
    stats["device"] = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "dtype": jnp.dtype(dtype).name,
        "engines_sharing": len(stacks),
    }
    print(json.dumps({"fanout_stats": stats}), file=sys.stderr)
    return 0 if stats["outcomes"].get("ok", 0) == resources else 1
