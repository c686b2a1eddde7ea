"""Device time of one model pass spent in the routed experts this chip holds: the loop over the blocks of sorted assignments, three int8 matmuls a block, and the weighted gather back (``moe_experts``).

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
        return scope_reduce.scope_ms_per_pass(ctx, 'moe_experts')
    except KeyError:        # the family of this cell adds no such scope
        return None
