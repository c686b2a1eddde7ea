#!/usr/bin/env python3
"""Run one cell of the benchmark and also say how full its mixed steps were.

    python3 scripts/step_fill.py --workload <cell> --seed <n> --seconds 45 --trace 0

``benchmarks/run.py`` as it is, with one more ``[bench]`` line before the
result: the window's delta of ``opsagent_step_tokens_total`` by kind, real /
computed (the fill share), of ``opsagent_mixed_dispatch_width_total`` by
width (the rows the dense segments of each mixed dispatch ran over) with
the share of dispatches that ran at half the step's tokens, the step
clock's mixed samples by chunk bucket (how many, and their mean device
time), the forced tokens the grammar spliced, the mixed dispatches
counted, and ``opsagent_kv_write_rows_total`` by kind with its ratio (the
rows the page write's scatter walked a layer for each token that landed). Until a ``benchmark`` PR gives the fill share a reader under
``benchmarks/layer_metrics/`` (PERF.md section 7), this is how a builder
reads it on the chip. A program without the counter prints zeros.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import client, run  # noqa: E402


def main() -> int:
    print_result = run.print_result
    mismatches = run.check.precision_mismatches
    impl: dict = {}

    def keep_impl(precision: dict, said: dict) -> list[str]:
        impl.update(said)     # the server's impl_info(), as run.py read it
        return mismatches(precision, said)

    def with_fill(result: dict, got: dict) -> None:
        before, after = got["ctx"]["before"], got["ctx"]["after"]

        def d(name: str, **labels) -> float:
            return client.delta(before, after, name, **labels)

        real = d("opsagent_step_tokens_total", kind="real")
        computed = d("opsagent_step_tokens_total", kind="computed")
        by_width = "opsagent_mixed_dispatch_width_total"
        widths = {
            labels["width"]: d(by_width, **labels)
            for labels, _ in after.get(by_width, [])}
        counted = sum(widths.values())
        clock = "opsagent_step_device_seconds"
        buckets = {}    # chunk bucket -> [mixed steps sampled, mean ms]
        for labels, _ in after.get(clock + "_count", []):
            if labels.get("program") == "mixed":
                n = d(clock + "_count", **labels)
                ms = d(clock + "_sum", **labels) / n * 1e3 if n else None
                buckets[labels["bucket"]] = [n, ms]
        # impl.step_rows is the engine's own word: "packed:N" where its
        # widest mixed program packs, and N // 2 is then the width of a
        # tick that carries no more (a rows program of exactly that many
        # slots would count beside it: no cell has one)
        how, _, packed = impl.get("step_rows", "rows").partition(":")
        narrow = str(int(packed) // 2) if how == "packed" else None
        landed = d("opsagent_kv_write_rows_total", kind="real")
        walked = d("opsagent_kv_write_rows_total", kind="scattered")
        run.say("step fill: " + json.dumps({
            "real": real, "computed": computed,
            "fill_share": real / computed if computed else None,
            "dispatches_by_width": widths,
            "step_rows": impl.get("step_rows"),
            "narrow_share": widths.get(narrow, 0.0) / counted
            if counted and narrow else None,
            "mixed_steps_by_bucket": buckets,
            "ffwd_tokens": d("opsagent_ffwd_tokens_total"),
            "mixed_dispatches": d(
                "opsagent_mixed_dispatch_decode_lanes_count"),
            "kv_write": impl.get("kv_write"),
            "kv_write_rows": {"scattered": walked, "real": landed},
            "kv_write_rows_per_token": walked / landed if landed else None,
        }))
        print_result(result, got)

    run.print_result = with_fill
    run.check.precision_mismatches = keep_impl
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
