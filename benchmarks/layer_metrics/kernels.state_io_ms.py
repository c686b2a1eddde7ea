"""Device time of one model pass spent in moving recurrent state: the rows' slot gather and scatter inside a step, snapshot copies inside a step and the copies dispatched on their own (a restore at admission) (``state_io``).

Layer: kernels (ops/linear_attention.py, models/llama.py and what XLA makes
of them). Source: the device trace: own time of each operation, charged to
the innermost ``jax.named_scope`` name on its ``tf_op`` path
(``benchmarks/scope_reduce.py``; the name is one the cell's family adds,
``families/solar_open2.py`` ``SCOPES``), over the model passes of the traced
span. With the seven ``kernels.*_ms`` every cell reads, whose ``ffn``,
``attn_qkv`` and ``attn_out`` hold what is left of a layer, the five of this
family add up to ``step.device_ms_mean`` less the device's idle share. A
program without the scope (the parent's) gives nothing to read.
Moves: tpot_p50_ms.
"""
from benchmarks import scope_reduce


def read(ctx: dict):
    try:
        return scope_reduce.scope_ms_per_pass(ctx, 'state_io')
    except KeyError:        # the family of this cell adds no such scope
        return None
