"""Device time of one model pass spent in the absorbed form's two per-head products against ``wukv``: a head's query content times ``W_uk`` on the way into the attention over the latent, and the attended latent times ``W_uv`` on the way out (``mla_absorb``).

Layer: kernels (models/llama.py ``_mla_latent_parts`` and ``_mla_latent_out``
and what XLA makes of them). Source: the device trace: own time of each
operation, charged to the innermost ``jax.named_scope`` name on its
``tf_op`` path (``benchmarks/scope_reduce.py``; the name is one the cell's
family adds, ``families/glm4_moe_lite.py`` ``SCOPES``), over the model
passes of the traced span. With the seven ``kernels.*_ms`` every cell reads,
whose ``attn_qkv``, ``attn_out`` and ``ffn`` hold what is left of a layer,
the four of this family add up to ``step.device_ms_mean`` less the device's
idle share. A program without the scope (the parent's) gives nothing to
read. Moves: tpot_p50_ms.
"""
from benchmarks import scope_reduce


def read(ctx: dict):
    try:
        return scope_reduce.scope_ms_per_pass(ctx, "mla_absorb")
    except KeyError:        # the program has no such scope
        return None
