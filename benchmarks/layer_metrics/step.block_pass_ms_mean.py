"""Device time of one model pass inside a fused decode block, over the
whole window: the pure-decode ticks that the three-second traced span of
``step.device_ms_mean`` may miss altogether.

Layer: model step (models/llama.py through serving/engine.py's block
pipeline). Source: the program's histogram ``opsagent_step_device_seconds``
with ``program="decode_block"``, ``_sum`` of the window's delta over
``_count`` times the configuration's ``decode_block`` passes a block.
Moves: tpot_p50_ms.
"""
from benchmarks.client import delta

FAMILY = "opsagent_step_device_seconds"


def read(ctx: dict):
    n = delta(ctx["before"], ctx["after"], FAMILY + "_count", program="decode_block")
    if n <= 0:
        return None
    total = delta(ctx["before"], ctx["after"], FAMILY + "_sum", program="decode_block")
    return total / (n * ctx["config"]["engine"]["decode_block"]) * 1e3
