#!/usr/bin/env python3
"""Run one cell of the benchmark and also say how full its mixed steps were.

    python3 scripts/step_fill.py --workload <cell> --seed <n> --seconds 45 --trace 0

``benchmarks/run.py`` as it is, with one more ``[bench]`` line before the
result: the window's delta of ``opsagent_step_tokens_total`` by kind, real /
computed (the fill share), the forced tokens the grammar spliced and the
mixed dispatches counted. Until a ``benchmark`` PR gives the fill share a
reader under ``benchmarks/layer_metrics/`` (PERF.md section 7), this is how
a builder reads it on the chip. A program without the counter prints zeros.
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

    def with_fill(result: dict, got: dict) -> None:
        before, after = got["ctx"]["before"], got["ctx"]["after"]

        def d(name: str, **labels) -> float:
            return client.delta(before, after, name, **labels)

        real = d("opsagent_step_tokens_total", kind="real")
        computed = d("opsagent_step_tokens_total", kind="computed")
        run.say("step fill: " + json.dumps({
            "real": real, "computed": computed,
            "fill_share": real / computed if computed else None,
            "ffwd_tokens": d("opsagent_ffwd_tokens_total"),
            "mixed_dispatches": d(
                "opsagent_mixed_dispatch_decode_lanes_count"),
        }))
        print_result(result, got)

    run.print_result = with_fill
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
