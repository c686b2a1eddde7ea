"""Sharded training step (fine-tuning path) for the llama-family models.

The reference has no training capability at all — its "model" is a remote
HTTPS API (reference pkg/llms/openai.go:69). In the TPU-native framework the
model is in-tree, so fine-tuning the served model (e.g. on recorded ops
transcripts to specialize tool-calling) becomes a first-class capability.

Design, tpu-first:

- One jitted train step: loss -> grad -> optax update. Everything inside is
  a single XLA program; no per-layer Python.
- Sharding is declarative: params/opt-state carry the same Megatron-style
  PartitionSpecs as serving (``models.llama.param_specs``); the batch is
  sharded over ``dp`` and the sequence over ``sp``. XLA inserts the psum for
  the gradient all-reduce over dp and the attention collectives over sp.
- Rematerialization (``jax.checkpoint``) on the scanned layer body trades
  FLOPs for HBM, which is what makes long-sequence fine-tuning fit.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import llama
from ..models.config import ModelConfig
from ..parallel.mesh import shard_params


@dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 1e-5
    weight_decay: float = 0.01
    b1: float = 0.9
    b2: float = 0.95
    grad_clip: float = 1.0
    remat: bool = True
    # Weight of the MoE router load-balance loss (Switch-style E·Σ f_e·P_e):
    # without it top-k routing is winner-take-all and experts die during
    # fine-tuning. Ignored (aux is 0) for dense models.
    moe_aux_weight: float = 0.01
    # Ring attention over the sp axis (context parallelism): K/V shards
    # rotate via ppermute instead of XLA's default all-gather of the whole
    # sequence — peak memory O(S/sp) per device, enabling sequences that
    # cannot fit gathered. No-op on meshes with sp=1.
    ring_attention: bool = False
    # Microbatches for GPipe pipelining when the mesh has pp > 1 (see
    # parallel/pipeline.py). Bubble fraction = (pp-1)/(microbatches+pp-1).
    pp_microbatches: int = 4


def cross_entropy_loss(
    logits: jax.Array,    # [B, S, V] float32
    targets: jax.Array,   # [B, S] int32
    mask: jax.Array,      # [B, S] float/bool — 0 for padding positions
) -> jax.Array:
    """Token-mean masked cross entropy, accumulated in float32."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def make_optimizer(tc: TrainConfig) -> optax.GradientTransformation:
    return optax.chain(
        optax.clip_by_global_norm(tc.grad_clip),
        optax.adamw(
            tc.learning_rate, b1=tc.b1, b2=tc.b2, weight_decay=tc.weight_decay
        ),
    )


def init_train_state(
    cfg: ModelConfig,
    tc: TrainConfig,
    mesh: Mesh,
    key: jax.Array,
    dtype: jnp.dtype = jnp.bfloat16,
    params: Any | None = None,
) -> tuple[Any, Any]:
    """(params, opt_state), both placed on the mesh. The optimizer moments
    are created with ``zeros_like`` over already-sharded params, so they
    inherit the parameter shardings with no extra spec tree."""
    if params is None:
        params = llama.init_params(cfg, key, dtype=dtype)
    params = shard_params(params, train_param_specs(cfg, mesh), mesh)
    opt_state = jax.jit(make_optimizer(tc).init)(params)
    return params, opt_state


def train_param_specs(cfg: ModelConfig, mesh: Mesh) -> Any:
    """Parameter PartitionSpecs for training on this mesh: pp-staged layer
    stacks when the mesh pipelines, the serving specs otherwise. Validates
    pipelineability HERE so unsupported configs fail with a clear error at
    state-init time, not a cryptic device_put divisibility failure."""
    pp = mesh.shape.get("pp", 1)
    if pp > 1:
        if cfg.moe is not None:
            _, lm = llama._layer_split(cfg)
            if lm % pp:
                raise ValueError(
                    f"moe layers {lm} not divisible by pp={pp}"
                )
        elif cfg.num_layers % pp:
            raise ValueError(
                f"num_layers={cfg.num_layers} not divisible by pp={pp}"
            )
        from ..parallel.pipeline import param_specs_pp

        return param_specs_pp(cfg)
    return llama.param_specs(cfg)


def make_train_step(
    cfg: ModelConfig,
    tc: TrainConfig,
    mesh: Mesh,
    dtype: jnp.dtype = jnp.bfloat16,
):
    """Build the jitted train step.

    step(params, opt_state, tokens [B,S], loss_mask [B,S]) ->
        (params, opt_state, metrics dict)

    ``tokens`` is next-token-shifted internally; ``loss_mask`` marks which
    *target* positions count (e.g. assistant turns only, for transcript
    fine-tuning). Data enters sharded P(dp, sp).
    """
    opt = make_optimizer(tc)
    data_sharding = NamedSharding(mesh, P("dp", "sp"))
    prefill_attn = None
    if tc.ring_attention and mesh.shape.get("sp", 1) > 1:
        from ..parallel.ring import make_ring_attention

        prefill_attn = make_ring_attention(mesh)

    if mesh.shape.get("pp", 1) > 1:
        # GPipe microbatch pipeline over the pp axis (parallel/pipeline.py);
        # params must carry param_specs_pp (init_train_state does). With
        # sp > 1 the stage runs ring attention over the sp axis inside
        # the pipeline's own shard_map (pp x sp composition — long-context
        # training across pipeline stages).
        from ..parallel.pipeline import make_pipeline_loss

        loss_fn = make_pipeline_loss(
            cfg, mesh, tc.pp_microbatches, dtype=dtype, remat=tc.remat,
            moe_aux_weight=tc.moe_aux_weight,
        )
    else:
        def loss_fn(params, tokens, loss_mask):
            # Attention runs over the full (evenly sp-shardable) sequence;
            # the next-token shift happens on the logits. Slicing tokens to
            # an odd length BEFORE the model makes XLA pad the sp shards
            # unevenly, and the padded attention lanes (scores -1e30,
            # squared in the backward) overflow to inf -> NaN grads.
            # Shift-at-the-loss avoids it.
            logits, aux = llama.forward_full(
                params, cfg, tokens, dtype=dtype, remat=tc.remat,
                return_aux=True, prefill_attn=prefill_attn,
            )
            ce = cross_entropy_loss(
                logits[:, :-1], tokens[:, 1:], loss_mask[:, 1:]
            )
            return ce + tc.moe_aux_weight * aux, (ce, aux)

    def step(params, opt_state, tokens, loss_mask):
        (_, (ce, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, loss_mask
        )
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        gnorm = optax.global_norm(grads)
        return params, opt_state, {
            "loss": ce, "moe_aux": aux, "grad_norm": gnorm,
        }

    jitted = jax.jit(
        step,
        in_shardings=(None, None, data_sharding, data_sharding),
        donate_argnums=(0, 1),
    )

    def run(params, opt_state, tokens, loss_mask):
        with jax.set_mesh(mesh):
            return jitted(params, opt_state, tokens, loss_mask)

    return run
