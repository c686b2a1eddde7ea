"""Device time of one model pass spent making the two latents: the query's and the keys' down-projections, their RMSNorms, the shared rotary key and the assembly of the page row ``[c_kv | k_r]`` (``mla_latent``).

Layer: kernels (models/llama.py ``_mla_q``, ``_mla_kv_latent``,
``_mla_latent_parts`` and what XLA makes of them). Source: the device trace:
own time of each operation, charged to the innermost ``jax.named_scope``
name on its ``tf_op`` path (``benchmarks/scope_reduce.py``; the name is one
the cell's family adds, ``families/glm4_moe_lite.py`` ``SCOPES``), over the
model passes of the traced span. In a packed mixed step these run over the
ROWS' slots, not the tick's tokens (an MLA mixer is handed rows). A program
without the scope (the parent's) gives nothing to read.
Moves: tpot_p50_ms.
"""
from benchmarks import scope_reduce


def read(ctx: dict):
    try:
        return scope_reduce.scope_ms_per_pass(ctx, "mla_latent")
    except KeyError:        # the program has no such scope
        return None
