"""Device time of one model pass spent in the ends of the model: embedding
lookup, final norm and vocabulary projection, FSM mask and sampling
(``embed`` + ``lm_head`` + ``sample``).

Layer: kernels (ops/attention.py, models/llama.py and what XLA makes of
them). Source: the device trace: own time of each operation, charged to the
innermost ``jax.named_scope`` name on its ``tf_op`` path
(``benchmarks/scope_reduce.py``), over the model passes of the traced span.
The seven ``kernels.*_ms`` add up to ``step.device_ms_mean`` less the
device's idle share. Moves: tpot_p50_ms.
"""
from benchmarks import scope_reduce


def read(ctx: dict):
    return scope_reduce.scope_ms_per_pass(ctx, 'embed', 'lm_head', 'sample')
