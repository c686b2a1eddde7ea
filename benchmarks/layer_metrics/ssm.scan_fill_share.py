"""How full Jamba's selective scan runs: the recurrence steps that carried a
live token over those the step programs ran.

Layer: model step (models/llama.py ``_mamba_mixer``, serving/engine.py
``_count_scan``). Source: the window's delta of
``opsagent_ssm_scan_steps_total{kind="real"}`` over that of
``{kind="computed"}``: under XLA the scan walks every slot of every row of a
step program (rows x slots a pass, times the 26 Mamba layers), whatever the
rows carry; the scan kernel walks a row's own tokens, and reads 100%. A program whose model has no Mamba layer counts neither, and
gives nothing to read. Moves: tpot_p50_ms.
"""
from benchmarks.client import delta

STEPS = "opsagent_ssm_scan_steps_total"


def read(ctx: dict):
    computed = delta(ctx["before"], ctx["after"], STEPS, kind="computed")
    if computed <= 0:
        return None
    return 100.0 * delta(
        ctx["before"], ctx["after"], STEPS, kind="real") / computed
