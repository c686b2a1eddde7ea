"""Device time of one model pass spent in the selective scan of Jamba's 26 Mamba layers: the decay ``exp(dt A)``, the input ``dt B x``, the read-out over the 16 state indices and the skip, for every slot the program's scan walks (``ssm_scan``).

Layer: kernels (ops/selective_scan.py, models/llama.py ``_mamba_mixer`` and what XLA
makes of them). Source: the device trace: own time of each
operation, charged to the innermost ``jax.named_scope`` name on its ``tf_op``
path (``benchmarks/scope_reduce.py``; the name is one the cell's family
adds, ``families/jamba.py`` ``SCOPES``), over the model passes of the traced
span. A program without the scope (the parent's, or another family's) gives
nothing to read. Moves: tpot_p50_ms.
"""
from benchmarks import scope_reduce


def read(ctx: dict):
    try:
        return scope_reduce.scope_ms_per_pass(ctx, 'ssm_scan')
    except KeyError:        # the family of this cell adds no such scope
        return None
