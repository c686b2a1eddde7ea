"""Pipeline parallelism (the ``pp`` mesh axis): GPipe-style microbatch
pipelining of the layer stack for training.

The reference has no distributed layer at all (SURVEY.md §2.2); pp exists
in this framework so the train step scales across the slow links: the mesh
lays ``pp`` outermost (parallel/mesh.py), so stages map onto DCN across
hosts/slices while each stage's tp/sp collectives stay on intra-slice ICI
— activations cross the slow link once per stage boundary per microbatch,
which is the only traffic pattern that tolerates DCN latency.

tpu-first shape of the implementation:

- ``jax.shard_map`` manual ONLY over ``pp`` (``axis_names={'pp'}``): the
  pipeline schedule — who computes what, when activations move — is
  explicit ``ppermute``; everything else (dp batch sharding, tp Megatron
  splits, sp sequence sharding) stays on GSPMD auto-sharding inside the
  stage, exactly as in the non-pipelined step.
- The schedule is one ``lax.scan`` over M + PP - 1 ticks (static trip
  count — no data-dependent Python control flow). At tick t, stage s runs
  microbatch t - s; activations advance one stage per tick via a
  non-cyclic ``ppermute``. The carry IS the pipeline register between
  stages.
- Stage-local layers: the stacked layer arrays are sharded over ``pp`` on
  their leading (layer) axis (``param_specs_pp``), so each stage scans its
  own L/PP layers — the same single traced layer body as the non-pipelined
  path (models/llama._run_stack).
- The loss head runs replicated after the scan: only the last stage's
  collected activations are final-layer outputs; a scalar ``psum`` over
  ``pp`` selects its loss sums. Non-last stages collect their OWN stage
  outputs (mid-stack activations), compute a meaningless loss from them,
  and have it zeroed by the ``where`` before the psum. This is safe
  because those activations are finite — embeddings or zeros through a
  finite-preserving stack — so neither the discarded forward value nor
  its cotangent (0 * finite in the VJP) can produce NaN. Any schedule
  extension must preserve that finiteness invariant: 0 * inf is NaN.

GPipe (synchronous) rather than interleaved/1F1B: the bubble is
(PP-1)/(M+PP-1), shrinking with more microbatches, and synchronous
scheduling composes with ``jax.grad`` as plain autodiff through the scan —
no hand-written backward schedule.

MoE models (the DeepSeek-class layout, VERDICT r2 weak #7): the layer
stack is dense-then-moe (``moe_layer_start`` dense layers, then MoE). The
MoE stack — where the weight is — shards over ``pp`` ((L - ms) %% PP == 0
required); the small dense prefix stays REPLICATED and logically belongs
to stage 0 (every stage computes it each tick and a ``where`` keeps only
stage 0's result — wasted FLOPs proportional to the 1-3 prefix layers,
in exchange for no special-cased stage program). Expert weights keep
their ``ep``/``tp`` axes inside each stage (GSPMD auto-sharding), so
EP x PP x TP compose on one mesh. The router load-balance aux is
accumulated only over each stage's VALID (non-bubble) microbatches and
averaged back to the non-pipelined scale.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models import llama
from ..models.config import ModelConfig
from .ring import _local_ring_attention


def param_specs_pp(cfg: ModelConfig) -> Any:
    """``models.llama.param_specs`` with the pipelined stack's leading
    (layer) axis sharded over ``pp``: each pipeline stage holds only its
    own layers. Embedding/head/final-norm stay replicated over pp (stage 0
    embeds, the last stage projects; replication keeps the spec simple and
    the arrays are small next to the layer stack). For MoE models only the
    MoE stack pipelines; the small dense prefix stays replicated (it runs
    on stage 0 — see the module docstring)."""
    specs = llama.param_specs(cfg)

    def stage_shard(spec: P) -> P:
        return P("pp", *spec[1:])

    if "moe_layers" in specs:
        specs["moe_layers"] = {
            k: stage_shard(s) for k, s in specs["moe_layers"].items()
        }
    else:
        specs["layers"] = {
            k: stage_shard(s) for k, s in specs["layers"].items()
        }
    return specs


def make_pipeline_loss(
    cfg: ModelConfig,
    mesh: Mesh,
    microbatches: int,
    dtype: jnp.dtype = jnp.bfloat16,
    remat: bool = False,
    moe_aux_weight: float = 0.0,
) -> Callable:
    """Build ``loss_fn(params, tokens [B,S], loss_mask [B,S]) ->
    (loss, (ce, aux))`` running the layer stack as a PP-stage pipeline.
    Drop-in for the trainer's loss path; params must be sharded with
    ``param_specs_pp``. Requires B %% microbatches == 0 and (dense models)
    L %% PP == 0 / (MoE models) (L - moe_layer_start) %% PP == 0.
    """
    PP = mesh.shape["pp"]
    SP = mesh.shape["sp"]
    M = microbatches
    is_moe = cfg.moe is not None
    Ld, Lm = llama._layer_split(cfg)
    if is_moe:
        if Lm % PP:
            raise ValueError(
                f"moe layers {Lm} not divisible by pp={PP}"
            )
    elif cfg.num_layers % PP:
        raise ValueError(
            f"num_layers={cfg.num_layers} not divisible by pp={PP}"
        )

    def run_stage(stage_params: Any, x: jax.Array, cos, sin):
        """Run a slice of the stack; returns (x, summed MoE aux).

        With sp > 1 the stage's sequence dim is the LOCAL shard and
        attention runs the sp-axis ppermute ring (parallel/ring.py) —
        pipeline stages and the ring compose because both are manual
        axes of the same shard_map."""
        mb, S = x.shape[:2]

        def attn_fn(h, lp, kc, vc, li):
            q, k, v = llama._qkv_rope(h, lp, cfg, cos, sin)
            if SP > 1:
                lengths = jnp.full((mb,), S * SP, jnp.int32)
                attn = _local_ring_attention(q, k, v, lengths, axis="sp")
            else:
                from ..ops.attention import causal_prefill_attention

                attn = causal_prefill_attention(q, k, v)
            return attn.reshape(mb, S, -1), kc, vc

        x, _, aux = llama._run_stack(
            stage_params, cfg, x, attn_fn, cache=None, remat=remat,
            stacks=tuple(stage_params),
        )
        return x, aux

    def pipelined(params, tokens, loss_mask):
        # Inside shard_map manual over (pp, dp): tokens are the per-dp-shard
        # slice, so microbatching divides the LOCAL batch.
        B, S = tokens.shape
        if B % M:
            raise ValueError(
                f"per-dp batch {B} not divisible by microbatches {M}"
            )
        mb = B // M
        stage = jax.lax.axis_index("pp")
        is_last = stage == PP - 1

        # Positions are GLOBAL: with sp > 1 this stage sees the local
        # sequence shard [sp_idx*S, (sp_idx+1)*S) of the full sequence.
        sp_idx = jax.lax.axis_index("sp")
        positions = (sp_idx * S + jnp.arange(S))[None, :].repeat(mb, axis=0)
        from ..ops.rope import rope_table

        cos, sin = rope_table(positions, cfg.rope_dim_, cfg.rope_theta,
                          scaling=cfg.rope_scaling)

        # Embedding is replicated over pp: every stage computes the same
        # xs, only stage 0's enters the pipeline (the where below).
        xs = params["embed"][tokens].astype(dtype)
        xs = xs.reshape(M, mb, S, -1)

        d = xs.shape[-1]
        # pcast: the carry starts as constant zeros but becomes varying
        # over the manual axes inside the scan (each stage and dp shard
        # holds different activations); the varying-manual-axes type must
        # match between scan input and output.
        outs0 = jax.lax.pcast(
            jnp.zeros((M, mb, S, d), dtype), ("pp", "dp", "sp"), to="varying"
        )
        reg0 = jax.lax.pcast(
            jnp.zeros((mb, S, d), dtype), ("pp", "dp", "sp"), to="varying"
        )  # pipeline register
        aux0 = jax.lax.pcast(
            jnp.zeros((), jnp.float32), ("pp", "dp", "sp"), to="varying"
        )

        def tick(carry, t):
            reg, outs, aux_acc = carry
            x_in = jnp.where(
                stage == 0, xs[jnp.clip(t, 0, M - 1)], reg
            )
            if is_moe:
                # The replicated dense prefix logically belongs to stage
                # 0: every stage computes it (Ld is 1-3 layers — cheap
                # next to the stage's Lm/PP MoE layers) and the where
                # keeps only stage 0's result, so all stages run one
                # uniform program. Prefix activations are finite, so the
                # discarded branch preserves the finiteness invariant.
                if Ld:
                    xd, _ = run_stage(
                        {"layers": params["layers"]}, x_in, cos, sin
                    )
                    x_in = jnp.where(stage == 0, xd, x_in)
                h, aux_t = run_stage(
                    {"moe_layers": params["moe_layers"]}, x_in, cos, sin
                )
            else:
                h, aux_t = run_stage(
                    {"layers": params["layers"]}, x_in, cos, sin
                )
            # Router aux only from REAL microbatches: during warmup/drain
            # ticks a stage chews zeros (bubble), whose routing stats
            # would pollute the load-balance signal.
            mb_idx = t - stage
            aux_acc = aux_acc + jnp.where(
                (mb_idx >= 0) & (mb_idx < M), aux_t, 0.0
            )
            # Advance the register one stage (non-cyclic: the last
            # stage's h leaves the pipeline into outs instead).
            reg = jax.lax.ppermute(
                h, "pp", [(i, i + 1) for i in range(PP - 1)]
            )
            out_idx = t - (PP - 1)
            valid = (out_idx >= 0) & (out_idx < M)
            outs = outs.at[jnp.where(valid, out_idx, M)].set(
                h, mode="drop"
            )
            return (reg, outs, aux_acc), None

        (_, outs, aux_acc), _ = jax.lax.scan(
            tick, (reg0, outs0, aux0), jnp.arange(M + PP - 1)
        )

        # Loss head, replicated: only the last stage's outs are final-layer
        # activations; a scalar psum over pp selects its sums. Other
        # stages' outs hold their own mid-stack activations — finite, so
        # the where-discarded loss (and its 0-scaled cotangent) stays
        # finite too. See the module docstring's finiteness invariant.
        x = outs.reshape(B, S, d)
        x = llama.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        logits = llama._lm_head(params, cfg, x)
        if SP > 1:
            # Next-token shift ACROSS the sp shard boundary: the target
            # of this shard's last position is the NEXT shard's first
            # token, fetched with one ppermute (the true last global
            # position has no target and is masked out).
            shift = [(j, j - 1) for j in range(1, SP)]
            nxt_tok = jax.lax.ppermute(tokens[:, :1], "sp", shift)
            nxt_msk = jax.lax.ppermute(
                loss_mask[:, :1].astype(jnp.float32), "sp", shift
            )
            last_shard = sp_idx == SP - 1
            targets = jnp.concatenate([tokens[:, 1:], nxt_tok], axis=1)
            msk = jnp.concatenate(
                [loss_mask[:, 1:].astype(jnp.float32),
                 jnp.where(last_shard, 0.0, nxt_msk)], axis=1
            )
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, targets[..., None], axis=-1
            )[..., 0]
        else:
            logz = jax.nn.logsumexp(logits[:, :-1], axis=-1)
            gold = jnp.take_along_axis(
                logits[:, :-1], tokens[:, 1:][..., None], axis=-1
            )[..., 0]
            msk = loss_mask[:, 1:].astype(jnp.float32)
        nll_sum = jnp.sum((logz - gold) * msk)
        tok_cnt = jnp.sum(msk)
        sums = jnp.where(
            is_last, jnp.stack([nll_sum, tok_cnt]), jnp.zeros((2,))
        )
        # Global token-mean: over the pipeline (pick the last stage's
        # sums), dp shards (each saw its own batch slice), and sp shards
        # (each saw its own sequence slice).
        sums = jax.lax.psum(sums, ("pp", "dp", "sp"))
        ce = sums[0] / jnp.maximum(sums[1], 1.0)
        # Aux back to the non-pipelined scale: each of the M microbatches
        # contributed its own per-layer routing stats (vs ONE whole-batch
        # stat in the unpipelined step), and dp/sp shards each counted
        # their slice — mean over all of them.
        aux = jax.lax.psum(aux_acc, ("pp", "dp", "sp")) / (
            M * mesh.shape["dp"] * mesh.shape["sp"]
        )
        return ce + moe_aux_weight * aux, (ce, aux)

    base_specs = llama.param_specs(cfg)
    param_in_specs = {
        "embed": P(),
        "final_norm": P(),
    }
    if is_moe:
        # Dense prefix replicated over pp; MoE stack pp-sharded on its
        # leading (layer) axis. ep/tp stay on GSPMD auto-sharding.
        if "layers" in base_specs:
            param_in_specs["layers"] = {
                k: P() for k in base_specs["layers"]
            }
        param_in_specs["moe_layers"] = {
            k: P("pp") for k in base_specs["moe_layers"]
        }
    else:
        param_in_specs["layers"] = {
            k: P("pp") for k in base_specs["layers"]
        }
    if not cfg.tie_embeddings:
        param_in_specs["lm_head"] = P()

    # Manual over pp, dp AND sp (tp/ep stay on GSPMD auto-sharding inside
    # the stage): dp must be manual here because XLA's SPMD partitioner
    # cannot yet mix an auto dp batch dimension with manual-pp collectives
    # (its AllReduceAlongShardingDims hits a device-group CHECK); sp is
    # manual so the stage can run the ring-attention ppermute over it.
    # Manual dp/sp is the same math — shard_map's transpose inserts the
    # gradient psum for the replicated params, exactly what GSPMD would
    # emit.
    return jax.shard_map(
        pipelined,
        mesh=mesh,
        in_specs=(param_in_specs, P("dp", "sp"), P("dp", "sp")),
        out_specs=(P(), (P(), P())),
        axis_names={"pp", "dp", "sp"},
    )
