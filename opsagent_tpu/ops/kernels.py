"""Which kernels an engine's step programs run: one value, ``Kernels``,
chosen once where the engine is built (``choose_kernels``) and handed to
every step function as one argument.

Each field is a NAME, not a bound function: ``Engine.impl_info()``, the
benchmark's checks and the tests print and compare names, and the
dispatchers where a kernel is called switch on them. "xla" everywhere is
plain ``jax.numpy``, correct on any backend and the oracle of every kernel's
tests. Three fields are the code's own choice from what it can observe (the
platform of the mesh's devices, the model's shapes, how its weights and
pages are stored): nothing outside the code names them. ``weights`` is a
request (``EngineConfig.weight_stream``) that the engine validates. A new
kernel costs one field here, one rule, and one branch where it is called.
"""

from __future__ import annotations

import os
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class Kernels(NamedTuple):
    """The name of what runs each of the four jobs that have a kernel."""

    attn: str = "xla"       # PAGED_BACKENDS: who reads paged keys and values
    state: str = "xla"      # STATE_BACKENDS: who updates the recurrent state
    experts: str = "xla"    # MOE_BACKENDS: who runs an expert share's blocks
    weights: str = "xla"    # WEIGHT_BACKENDS: how quantized weights stream


PAGED_BACKENDS = ("xla", "pallas-stream")
STATE_BACKENDS = ("xla", "pallas-state", "pallas-ssm")
MOE_BACKENDS = ("xla", "pallas-grouped")
WEIGHT_BACKENDS = ("xla", "pallas-dma")


def require_kernels(kernels: Kernels) -> None:
    """A name that is no kernel's (a deleted kernel's, a typo) is an error
    where the dispatch would otherwise run XLA under it."""
    for field, name, known in zip(Kernels._fields, kernels, (
            PAGED_BACKENDS, STATE_BACKENDS, MOE_BACKENDS, WEIGHT_BACKENDS)):
        if name not in known:
            raise ValueError(
                f"Kernels.{field}={name!r}: expected one of {known}")


def paged_attention_backend(
    *,
    platform: str,
    head_dim: int,
    kv_heads_per_shard: int,
    page_itemsize: int,
    mla: bool = False,
    shared_kv: bool = False,
    tp: int = 1,
) -> str:
    """Which reader of paged keys and values an engine runs: "xla" (the
    gather, and the oracle of every test) or "pallas-stream" (the
    streaming ragged kernel, ``paged_attention_stream``).

    The choice is the code's, a pure function of what it can observe
    where the engine is built: the platform of the mesh's devices and the
    shapes ``pallas_refusal`` takes, which are the READER's (an MLA model
    that holds the latent describes itself as what its reader is handed:
    one kv head of ``MLAConfig.page_dim`` lanes that is keys and values
    alike); nothing outside the code names a reader. On a TPU it is the
    streaming kernel wherever the chip's compiler takes it (head dim on
    the 128-lane tiling, bf16 pages; MLA's absorbed attention over latent
    pages among them); everywhere else (the CPU, int8 pages, head dims
    off the tiling, MLA with materialised heads, the latent under tp > 1)
    the gather. By measurement (PERF.md section 6, PRs 29 and 41,
    ``scripts/attn_microbench.py`` at the benchmark cells' shapes on a
    v5e): the kernel is ahead of the gather at every shape the cells run,
    decode blocks over short rows included, so no shape is sent back to
    the gather on speed."""
    if platform != "tpu":
        return "xla"
    refused = pallas_refusal(
        "pallas-stream", head_dim=head_dim,
        kv_heads_per_shard=kv_heads_per_shard,
        page_itemsize=page_itemsize, mla=mla, shared_kv=shared_kv, tp=tp,
    )
    return "xla" if refused else "pallas-stream"


def linear_state_backend(
    *,
    platform: str,
    state_dtype: str,
    key_dim: int,
    value_dim: int,
    heads: int,
) -> str:
    """Who updates a linear-attention layer's recurrent state in an
    engine's step programs: "xla" (a slot gathered a row at a time, the
    chunk form or the one-token recurrence in plain ``jax.numpy``, two
    scatters; the oracle of every test) or "pallas-state" (one kernel a
    layer from read through update to both writes,
    ``linear_state_pallas``).

    The code's own choice, as ``paged_attention_backend`` is, from what it
    can observe where the engine is built; the cache is then held in the
    form the answer reads (``llama.state_slot_shape``). On a TPU it is the
    kernel wherever a float32 state tile lies on whole (8, 128) tiles as
    held: the key dim a multiple of 8, and the value dim of one head, or
    of two heads side by side where the heads pair up, a multiple of 128
    (Mosaic takes no other; tests/test_tpu_compile_state.py compiles both
    of the benchmark's shapes). Everywhere else XLA."""
    if platform != "tpu" or state_dtype != "float32" or key_dim % 8:
        return "xla"
    if value_dim % 128 and (heads % 2 or (2 * value_dim) % 128):
        return "xla"
    return "pallas-state"


def ssm_state_backend(
    *, platform: str, state_dtype: str, d_state: int, d_inner: int
) -> str:
    """Who runs a Mamba layer's selective scan over the state slots in an
    engine's step programs: "xla" (a slot gathered a row at a time, a
    ``lax.scan`` over every slot of every row in plain ``jax.numpy``, two
    scatters; the oracle of every test) or "pallas-ssm" (one kernel a layer
    from read through the rows' own tokens to both writes,
    ``selective_scan_pallas``). The code's own choice, as
    ``linear_state_backend`` is, and the cache is held for it
    (``llama.slot_shapes``): the kernel on a TPU wherever the float32 state
    ``[d_state, d_inner]`` lies on whole (8, 128) tiles, everywhere else
    XLA."""
    if (platform != "tpu" or state_dtype != "float32" or d_state % 8
            or d_inner % 128):
        return "xla"
    return "pallas-ssm"


def moe_experts_backend(
    *,
    platform: str,
    quantize: str,
    hidden_size: int,
    expert_width: int,
    tp: int = 1,
    ep: int = 1,
) -> str:
    """Who runs the blocks of an expert share (``llama._moe_share``) in an
    engine's step programs: "xla" (a ``while`` over the blocks in use, an
    expert's three matmuls a block as fusion calls; the oracle of every
    test) or "pallas-grouped" (one kernel a layer over the whole sorted
    buffer, the next block's int8 tiles in flight while this block
    multiplies, ``moe_experts_pallas``).

    The code's own choice, as ``paged_attention_backend`` is, from what it
    can observe where the engine is built: on a TPU the kernel wherever
    the expert stacks are int8 leaves (``quantize`` "int8": per-channel
    scales) held whole on the one shard, with the model width and the
    experts' intermediate width on whole 128-lane tiles; everywhere else
    (the CPU, bfloat16 or int4 stacks, ``tp`` or ``ep`` above 1, widths
    off the lanes) the loop."""
    if (platform != "tpu" or quantize != "int8" or tp > 1 or ep > 1
            or hidden_size % 128 or expert_width % 128):
        return "xla"
    return "pallas-grouped"


def reader_shapes(
    model_cfg: Any, *, tp: int, dtype: Any, kv_quantize: str
) -> dict[str, Any]:
    """What the reader of a model's pages is handed, as
    ``paged_attention_backend`` and ``pallas_refusal`` take it: heads of
    ``head_dim_``, or, where MLA holds the latent, its one row of
    ``page_dim`` lanes that is keys and values alike under the absorbed
    queries."""
    mla = model_cfg.mla
    latent = mla is not None and mla.latent_cache
    return dict(
        head_dim=mla.page_dim if latent else model_cfg.head_dim_,
        kv_heads_per_shard=1 if latent else max(
            1, model_cfg.num_kv_heads // tp),
        page_itemsize=1 if kv_quantize else jnp.dtype(dtype).itemsize,
        mla=mla is not None, shared_kv=latent, tp=tp,
    )


def choose_kernels(
    model_cfg: Any,
    *,
    platform: str,
    tp: int = 1,
    ep: int = 1,
    dtype: Any = jnp.bfloat16,
    quantize: str = "",
    kv_quantize: str = "",
    state_dtype: str = "float32",
    weights: str = "xla",
) -> Kernels:
    """The ``Kernels`` of an engine, from what it can observe where it is
    built: the platform of its mesh's devices, its shards (``tp``, ``ep``),
    how weights and pages are stored, and the model's config. One rule a
    field, each asked only where the model has the job (a model without
    recurrent state or an expert share keeps "xla" there). ``weights`` is
    what was asked for, already validated by the caller."""
    attn = paged_attention_backend(platform=platform, **reader_shapes(
        model_cfg, tp=tp, dtype=dtype, kv_quantize=kv_quantize))
    state = "xla"
    if model_cfg.state_mixer == "linear":
        la = model_cfg.linear_attn
        state = linear_state_backend(
            platform=platform, state_dtype=state_dtype,
            key_dim=la.key_head_dim, value_dim=la.value_head_dim,
            heads=la.num_heads,
        )
    elif model_cfg.state_mixer == "mamba":
        mamba = model_cfg.mamba
        state = ssm_state_backend(
            platform=platform, state_dtype=state_dtype,
            d_state=mamba.d_state, d_inner=mamba.d_inner,
        )
    experts = "xla"
    if model_cfg.expert_share:
        experts = moe_experts_backend(
            platform=platform, quantize=quantize,
            hidden_size=model_cfg.hidden_size,
            expert_width=model_cfg.moe.expert_intermediate_size, tp=tp, ep=ep,
        )
    return Kernels(attn=attn, state=state, experts=experts, weights=weights)


def pallas_interpret() -> bool:
    """Whether the Pallas kernels should run in interpret mode
    (OPSAGENT_PALLAS_INTERPRET=1): how the CPU tests run the attention
    and weight-stream kernels' dispatch paths end-to-end off-TPU, where a
    compiled pallas_call cannot lower. Read at trace time by the ``*_auto``
    dispatchers. On the chip it is an error, not a slow success:
    interpret mode is orders of magnitude slower and skips Mosaic
    entirely, so whatever it produced there would carry the kernel's
    name without having run the kernel."""
    on = os.environ.get("OPSAGENT_PALLAS_INTERPRET", "") == "1"
    if on and jax.default_backend() == "tpu":
        raise RuntimeError(
            "OPSAGENT_PALLAS_INTERPRET=1 on the tpu backend: interpret "
            "mode is for CPU tests only; unset it"
        )
    return on


def pallas_refusal(
    impl: str,
    *,
    head_dim: int,
    kv_heads_per_shard: int,
    page_itemsize: int,
    mla: bool = False,
    shared_kv: bool = False,
    tp: int = 1,
) -> str | None:
    """Why paged-attention backend ``impl`` cannot serve these shapes, or
    None when it can. The shapes are what the READER is handed: for an
    MLA model that holds the latent, one kv head of ``page_dim`` lanes
    whose pages are keys and values alike (``shared_kv``), under the
    absorbed queries. What the streaming kernel has no reader for: MLA
    with materialised heads (``mla`` without ``shared_kv``), the latent
    under ``tp`` > 1, int8 pages, and a head dim off the 128 lanes
    (Mosaic's refusal when the kernel was compiled for a described v5e
    device; tests/test_tpu_compile_attention.py keeps both sides of each
    rule). ``paged_attention_backend`` sends such an engine to the gather,
    so no such combination reaches the chip to fail there. Interpret mode
    has no Mosaic and not its tiling limit.

    ``page_itemsize``: bytes per stored KV element (1 for int8 pages).
    ``kv_heads_per_shard``: one of the shapes an engine describes itself
    by; no rule reads it, the kernel takes any head count."""
    if impl == "xla":
        return None
    if mla and not shared_kv:
        return (
            f"paged backend {impl!r} with MLA's materialised heads (no "
            "latent cache): keys of nope + rope dims (192 or 256 wide) "
            "beside narrower values padded to them, a form the kernel has "
            "never been compiled for or run at; it serves through the "
            "xla gather"
        )
    if shared_kv and tp > 1:
        return (
            f"paged backend {impl!r} over pages that are keys and values "
            f"alike (MLA's latent) at tp={tp}: one replicated kv head "
            "under sharded query heads has never been compiled or run "
            "inside the kernel's shard_map; it serves through the xla "
            "gather"
        )
    if page_itemsize == 1:
        return (
            "pallas-stream with int8 pages: a 16-token page is half "
            "of an int8 tile's 32 rows, so a key block cannot be "
            "read out of the page buffers without a re-tiling, and "
            "the per-token scales would need a lane-to-sublane move "
            "a kv head; QuantizedPages serve through the xla gather"
        )
    if head_dim % 128:
        return (
            f"pallas-stream with head_dim {head_dim}: a kv head is a "
            "slice of the merged page row's lanes, and Mosaic wants "
            "it on the 128-lane tiling; such heads serve through the "
            "xla gather"
        )
    return None
