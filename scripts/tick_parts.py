#!/usr/bin/env python3
"""Run one cell of the benchmark and also read the host's side of its ticks.

    python3 scripts/tick_parts.py --workload <cell> --seed <n> --seconds 45 --trace 0

``benchmarks/run.py`` as it is, with more ``[bench]`` lines before the
result. ``run.py`` calls the per-layer readers in a traced run only; the
readers that need nothing but the two scrapes around the window (``source``
``program_counter`` in BENCHMARK.json) read the same in a plain run, whose
host is not slowed by the profiler, so this calls them there: each prints
what it prints (the phase table, the whole part table, the admission
attempts, the step clock by width) and the values go on one line,
``[bench] host readers: {...}``. Against a program from before the parts
the readers of new families give nothing and are left out of the line.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.loading import load_module  # noqa: E402


def main() -> int:
    print_result = run.print_result
    load_cell = run.load_cell
    cell: dict = {}

    def keep_cell(workload: str, rehearse: bool) -> dict:
        cell.update(load_cell(workload, rehearse))
        return cell

    def with_host_readers(result: dict, got: dict) -> None:
        values = {}
        for m in cell["per_layer"]:
            if m["source"] != "program_counter":
                continue
            try:
                value = load_module("layer_metrics", m["name"]).read(
                    got["ctx"])
            except (KeyError, TypeError):
                continue    # a reader that wants the trace of a traced run
            if value is not None:
                values[m["name"]] = value
        run.say("host readers: " + json.dumps(values))
        print_result(result, got)

    run.load_cell = keep_cell
    run.print_result = with_host_readers
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
