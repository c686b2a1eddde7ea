"""Device time of one model pass spent in what surrounds the scan in Jamba's 26 Mamba layers: ``W_in``, the causal conv and its bias, ``W_x``, the three small norms, ``W_dt`` and its softplus, the gate, and the packing of tokens to rows and back (``ssm_proj``; ``W_out`` is ``attn_out``'s).

Layer: kernels (models/llama.py ``_mamba_mixer`` and what XLA makes of it). Source: the device trace: own time of each
operation, charged to the innermost ``jax.named_scope`` name on its ``tf_op``
path (``benchmarks/scope_reduce.py``; the name is one the cell's family
adds, ``families/jamba.py`` ``SCOPES``), over the model passes of the traced
span. A program without the scope (the parent's, or another family's) gives
nothing to read. Moves: tpot_p50_ms.
"""
from benchmarks import scope_reduce


def read(ctx: dict):
    try:
        return scope_reduce.scope_ms_per_pass(ctx, 'ssm_proj')
    except KeyError:        # the family of this cell adds no such scope
        return None
