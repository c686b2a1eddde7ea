"""The serving engine: jitted prefill/decode over a paged KV cache.

This is the compute core that replaces the reference's remote LLM round trip
(reference pkg/assistants/simple.go:343,515 -> pkg/llms/openai.go:69). Design
points (SURVEY.md section 7):

- **Two XLA programs**: prefill (one sequence, bucketed lengths) and a
  fixed-batch decode step. Static shapes only — bucketing avoids
  recompilation; the decode batch is padded with inactive slots.
- **Paged KV cache**: device pages + host PageAllocator; the cache pytree is
  donated through every call, so it lives in HBM with no copies.
- **Tensor parallelism**: params/cache placed with NamedShardings over the
  (dp, sp, tp) mesh; jit propagates, XLA emits the ICI collectives.
- **Greedy-by-default sampling** on device, with per-request temperature /
  top-k / top-p and a constrained-decoding mask hook.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..models import llama
from ..models.config import ModelConfig, get_config_preset
from ..parallel.mesh import make_mesh, shard_params, spec_tree_shardings
from ..utils.logger import get_logger
from ..utils.perf import get_perf_stats
from ..utils.profiling import annotate
from .kvcache import InvalidRequest, PageAllocator, OutOfPages
from .sampler import SamplingParams, sample
from .tokenizer import Tokenizer, load_tokenizer

log = get_logger("engine")


_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compile_cache_dir() -> str:
    """THE location of the persistent XLA compilation cache:
    ``$JAX_COMPILATION_CACHE_DIR`` where the environment sets it, else
    ``<checkout>/.jax_cache/<platform tag>`` (git-ignored). The path is
    part of the cache key, so it is never a temporary name, a pid or a
    time. Everything that reads or packages the cache
    (``Engine.snapshot``, ``preseed_compile_cache``, ``chip_smoke.py``)
    resolves it through here.

    The default is tagged per platform, and for the CPU additionally by
    the host's feature set: XLA:CPU stores AOT machine code, and a tree
    copied between machines must never load an avx512-targeted entry on
    a host without those features (SIGILL, seen as cpu_aot_loader
    warnings) or a CPU entry on the chip machine."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    tag = jax.default_backend()
    if tag == "cpu":
        import hashlib

        try:
            with open("/proc/cpuinfo") as f:
                flags = next((ln for ln in f if ln.startswith("flags")), "")
            tag += "-" + hashlib.sha1(flags.encode()).hexdigest()[:8]
        except OSError:
            pass
    return os.path.join(_CHECKOUT, ".jax_cache", tag)


def enable_compilation_cache() -> str | None:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    so engine restarts reuse compiled prefill/decode programs instead of
    paying XLA compilation per bucket. Idempotent. Returns the active
    cache directory (what ``Engine.snapshot`` packages as a build
    artifact), or None when JAX's own ``jax_enable_compilation_cache``
    switch is off.

    Where ``JAX_COMPILATION_CACHE_DIR`` was set before JAX was imported,
    JAX already holds it and nothing is set here.
    ``OPSAGENT_COMPILE_CACHE_MIN_S`` overrides the minimum compile seconds
    persisted — ``snapshot create`` and the bench cold-start stage set it
    to 0 so every warmed program lands in the cache regardless of how
    fast it compiled."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    path = compile_cache_dir()
    try:
        os.makedirs(path, exist_ok=True)
    except OSError as e:
        log.warning("compilation cache unavailable at %s (%s)", path, e)
        return None
    if jax.config.jax_compilation_cache_dir != path:
        # The environment named the directory after JAX was imported
        # (tests), or named none. JAX materialises its cache object
        # lazily and keeps it for the life of the process, so drop it
        # before re-pointing.
        from jax.experimental.compilation_cache import (
            compilation_cache as cc,
        )

        cc.reset_cache()
        cc.set_cache_dir(path)
    # Default threshold skips small programs; the TTFT budget cares
    # about every bucket, so cache anything that took >=1 s to build.
    try:
        min_s = float(os.environ.get("OPSAGENT_COMPILE_CACHE_MIN_S", "1.0"))
    except ValueError:
        min_s = 1.0
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)
    return path


def _host_cpu_device():
    """The host CPU device that checkpoint weights are loaded and
    quantized on before only the narrow tree crosses to the accelerator.
    ``JAX_PLATFORMS=tpu`` (the chip machine's start-up default) leaves
    the CPU platform uninitialised, and ``jax.local_devices(backend=
    "cpu")`` then raises an error that names neither cause nor cure."""
    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "loading a checkpoint with --quantize needs the host CPU "
            "backend beside the accelerator: start with "
            "JAX_PLATFORMS=tpu,cpu (or unset), not "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}"
        ) from e


def _merge_pulls(out: dict[int, list[int]], pulled: dict[int, list[int]]) -> None:
    """Fold one pulled block's tokens into an accumulated result. Plain
    dict.update would REPLACE a sequence's list when several pulled blocks
    carry tokens for it (multi-block drains), dropping tokens."""
    for sid, toks in pulled.items():
        out.setdefault(sid, []).extend(toks)


class BackendRefused(ValueError):
    """An explicitly requested kernel backend that cannot run this
    configuration; the message is the compiler's (or the kernel's) reason."""


@dataclass
class EngineConfig:
    model: str = "tiny-test"
    checkpoint: str = ""
    tokenizer: str = ""
    dtype: Any = jnp.bfloat16
    tp: int = 0                      # 0 = all devices
    dp: int = 1
    # Sequence/context parallelism for PREFILL: with sp > 1 each prefill
    # chunk's attention runs as ragged ring attention sharded over the sp
    # mesh axis (parallel.ring), spreading the chunk's O(S^2) attention
    # over sp devices — covering prompts up to the largest bucket in one
    # sharded pass (BASELINE config 4 scale). Prefix-tail chunks (prompts
    # beyond the largest bucket, or prefix-cache hits) attend over paged
    # cache and stay on the pjit-partitioned gather path; decode has no
    # sequence axis to shard.
    sp: int = 1
    # Expert parallelism for MoE models: experts (weights AND grouped-
    # dispatch compute) shard over the ep mesh axis (DeepSeek-V3-class
    # scale-out). No effect on dense models.
    ep: int = 1
    # Max admitting sequences prefilled per batched dispatch (scheduler
    # groups same-bucket chunks; rows pad to powers of two). Divides
    # per-session TTFT under concurrent admissions by up to this factor.
    prefill_batch: int = 4
    page_size: int = 16
    num_pages: int = 2048
    max_pages_per_seq: int = 320   # 5120 tokens: largest bucket + generation
    max_batch_size: int = 8
    # Decode steps fused into one device dispatch (1 = step-at-a-time).
    # Each dispatch costs a host->device round trip plus ONE device->host
    # token pull, so per-token overhead scales as RTT / decode_block.
    decode_block: int = 32
    # Dispatches allowed in flight beyond the one being pulled. With the
    # decode loop state device-resident (decode_loop.decode_block_carry),
    # block k+1..k+depth are enqueued before block k's tokens are pulled,
    # so the pull RTT and host bookkeeping overlap device compute. 0 =
    # synchronous (pull immediately after each dispatch).
    pipeline_depth: int = 2
    prefill_buckets: tuple[int, ...] = (64, 128, 256, 512, 1024, 2048, 4096)
    # Mixed prefill+decode batching (Sarathi-style piggybacking over the
    # ragged paged-attention op): the scheduler tick packs every decode
    # lane (one token each) PLUS up to max_step_tokens of chunked-prefill
    # tokens into ONE device dispatch (step_mixed), so prefill rides the
    # decode dispatch's weight stream instead of buying its own — on a
    # weight-streaming-bound model the split tick streams ~all weights
    # TWICE per tick (one prefill program + one decode program). Decode
    # lanes get budget first; the remainder goes to the oldest admitting
    # prompts. The split path remains the fallback (flag off, no admitting
    # prompts, or rows needing host-side per-token work).
    mixed_batching: bool = True
    # Per-mixed-dispatch token budget: decode lanes (1 token each) are
    # funded first, remaining budget seats prefill chunk tokens.
    max_step_tokens: int = 256
    # Chunk-size buckets for the mixed program's query axis: the chunk
    # pads to the smallest bucket holding it, so XLA compiles one program
    # per bucket (warmed by warmup()) and the post-warmup-zero-compiles
    # invariant survives arbitrary batch compositions. Kept modest — the
    # ragged Pallas kernel's VMEM accumulator scales with the bucket.
    mixed_buckets: tuple[int, ...] = (16, 32, 64, 128)
    # One-step-lookahead ASYNC mixed ticks (serving/async_runtime.py):
    # depth of the mixed-tick dispatch pipeline. At 2 (default) tick
    # t+1's dispatch is enqueued BEFORE tick t's tokens are pulled — the
    # decode lanes' sampled-token feedback stays device-resident (a
    # carry, like the block-decode loop) — so host post-processing
    # (detokenize, stop/EOS scan, streaming, trie bookkeeping) overlaps
    # device compute. Stop-string/EOS detection lags one tick: the
    # finished row's one overshoot token is discarded and its page
    # booking rolled back. 1 = today's synchronous tick. Constrained
    # rows ride the async lane only with dense device FSM tables;
    # hosted-mask/logprobs/bias rows route to the sync lanes.
    async_depth: int = 2
    max_new_tokens_default: int = 1024
    seed: int = 0
    prefix_cache: bool = True
    # Hierarchical KV cache: the host-RAM offload tier (serving/offload).
    # With offload on, trie evictions SPILL page content to a bounded
    # host pool instead of dropping it, tool-time parking
    # (park_chain / park_sequence) proactively frees HBM while a session
    # blocks on tool execution, and admission RESTORES pooled pages with
    # a device copy instead of re-prefilling them. Requires prefix_cache.
    offload: bool = False
    # Pages per copy dispatch (the copy engine's largest bucket); page-id
    # vectors pad to (1, offload_copy_pages) so the restore path stays
    # inside the zero-post-warmup-compiles invariant.
    offload_copy_pages: int = 8
    # Host pool byte bound; 0 = $OPSAGENT_KV_HOST_POOL_BYTES or 1 GiB.
    host_pool_bytes: int = 0
    # Weight-only quantization: "" (compute dtype) or "int8" (per-channel
    # symmetric, models.quant). Halves weight HBM traffic — the decode
    # bottleneck — and the footprint: Llama-3-8B fits a 16 GB v5e chip
    # only at int8.
    quantize: str = ""
    # KV-cache quantization: "" (pages in compute dtype) or "int8" (pages
    # int8 + per-token-per-head f32 scales, ops.attention.QuantizedPages).
    # Halves decode-step KV reads — the dominant non-weight HBM term at
    # serving shapes (PERF.md roofline: ~4 GB/step at the 8B bench
    # config). Served through the xla gather (the streaming kernel has no
    # reader for int8 pages); an MLA latent gets one scale a token.
    kv_quantize: str = ""
    # Weight-stream backend for the quantized decode/mixed hot path: ""
    # (resolve from $OPSAGENT_WEIGHT_STREAM, default "xla") or explicit
    # "xla" / "pallas-dma". "pallas-dma" streams int8/int4 weight tiles
    # HBM->VMEM through double-buffered DMA slots under the layer scan
    # (ops.quant_matmul_pallas) so layer l+1's weights load during layer
    # l's compute; "xla" keeps the fused dequantize-in-operand-read path.
    # Default xla BY MEASUREMENT policy (same rule as the paged-attention
    # backend): the ragged-sweep bench covers the axis, and the default
    # flips only on on-chip evidence. Resolved ONCE at engine init (into
    # Engine.kernels.weights): requires quantized weights and tp == 1, else
    # the engine refuses to start (BackendRefused); what runs is in
    # impl_info().
    weight_stream: str = ""
    # Grammar-accelerated decoding: when a constrained row's FSM state
    # admits exactly ONE legal token (JSON punctuation, known key names,
    # enum close-quotes), emit the whole forced run with NO per-token
    # forward pass — spliced as one multi-token append through the mixed
    # program's q_len>1 path. Acceptance = 1.0 by construction (the
    # masked sample can only produce the forced token), so greedy output
    # is byte-identical with the flag off; the skipped dispatches are
    # exact counts reported by opsagent_ffwd_*_total. Rows without dense
    # device FSM tables (hosted masks, budget-exceeded schemas), with
    # logprobs, or with logit bias are ineligible and decode normally.
    grammar_ffwd: bool = True
    # Recurrent state (a model with linear-attention layers; nothing is
    # allocated otherwise). Every row has a live slot (``max_batch_size``
    # of them). ``state_snapshots``: the pool of snapshot slots the prefix
    # trie restores from (0 = twice the live slots): each running sequence
    # writes one, and a donated chain keeps it on the node that ends it,
    # LRU-evicted when a new sequence needs a slot.
    state_snapshots: int = 0
    # Compile every serving program (all prefill buckets + decode) at
    # construction time so the first real request never pays XLA compile
    # (the TTFT budget is 500 ms; a cold bucket compile is tens of seconds).
    warmup: bool = False


@dataclass
class Sequence:
    """Host-side state of one in-flight generation."""

    seq_id: int
    prompt_len: int
    prompt_ids: list[int] = field(default_factory=list)
    tokens: list[int] = field(default_factory=list)   # generated tokens
    params: SamplingParams = field(default_factory=SamplingParams)
    done: bool = False
    finish_reason: str = ""        # "stop" | "length" | "preempted"
    mask_fn: Callable[[list[int]], np.ndarray] | None = None  # constrained decode
    stream: Callable[[int], None] | None = None
    ttft_s: float = 0.0
    started_s: float = field(default_factory=time.perf_counter)
    # Per generated token, when params.logprobs: {"logprob": float,
    # "top": [(token_id, logprob), ...][:params.top_logprobs]}.
    logprob_data: list[dict] = field(default_factory=list)
    # Cached static logit_bias row [V] (built on first use).
    static_bias: Any = None
    # Incremental {token_id: count} for presence/frequency penalties —
    # maintained by _accept_token so penalized long generations stay
    # O(distinct tokens) per step instead of re-counting the history.
    penalty_counts: dict | None = None
    # Observability (obs.trace): the request's span handle under which the
    # engine records prefill/decode phase children, the open decode span,
    # and the previous accepted-token timestamp for the inter-token-latency
    # histogram. All optional — untraced traffic pays one None check.
    trace: Any = None
    decode_span: Any = None
    last_tok_s: float = 0.0
    # The scheduler's tick count (Engine.sched_tick) at the first and at
    # the newest accepted token: their difference over the tokens less one
    # is the ticks a token took (opsagent_request_decode_ticks_total).
    first_tok_tick: int = 0
    last_tok_tick: int = 0


class Engine:
    """Single-process serving engine (thread-safe via one lock: JAX dispatch
    is serialized per device anyway; the scheduler provides concurrency)."""

    def __init__(
        self,
        cfg: EngineConfig,
        model_cfg: ModelConfig | None = None,
        params: Any | None = None,
        tokenizer: Tokenizer | None = None,
        params_quantized: bool = False,
    ):
        """``params_quantized``: the caller-supplied ``params`` tree is
        ALREADY in the quantized layout matching ``cfg.quantize`` (the
        snapshot-restore path) — apply ``quantize_specs`` only, never
        ``quantize_params`` (re-quantizing int8 weights would corrupt
        them)."""
        self.cfg = cfg
        self.compile_cache_dir = enable_compilation_cache()
        cache_entries = (
            len(os.listdir(self.compile_cache_dir))
            if self.compile_cache_dir else 0
        )
        self.model_cfg = model_cfg or get_config_preset(cfg.model)
        if self.model_cfg.moe is not None:
            # Serving pins the EXACT all-experts dispatch: the grouped
            # capacity path can drop assignments under skewed routing and
            # its activation depends on chunk token count, which varies
            # with prefix-cache residency — a request's output must not
            # depend on what happens to be cached. Training keeps grouped
            # dispatch (models.llama._moe_mlp).
            from dataclasses import replace

            self.model_cfg = replace(
                self.model_cfg,
                moe=replace(self.model_cfg.moe, grouped_dispatch_min_tokens=0),
            )
        self.tokenizer = tokenizer or load_tokenizer(
            cfg.tokenizer, vocab_size=self.model_cfg.vocab_size
        )
        n_dev = len(jax.devices())
        slots = cfg.dp * cfg.sp * cfg.ep
        if cfg.sp > 1:
            # Fail fast with the config knob named, instead of an opaque
            # shard_map divisibility error at first prefill.
            bad = [b for b in cfg.prefill_buckets if b % cfg.sp]
            if bad:
                raise ValueError(
                    f"sp={cfg.sp} must divide every prefill bucket "
                    f"(offending: {bad})"
                )
            if slots * max(1, cfg.tp) > n_dev:
                raise ValueError(
                    f"dp={cfg.dp} * sp={cfg.sp} * ep={cfg.ep} * "
                    f"tp={max(1, cfg.tp)} exceeds {n_dev} devices"
                )
        tp = cfg.tp if cfg.tp > 0 else max(
            1, n_dev // slots if n_dev % slots == 0 else 1
        )
        # kv heads AND the vocab (embedding/lm-head shard dim) must divide
        # cleanly over tp; fall back gracefully. Vocab matters for
        # HF-derived configs (config_from_hf): a tokenizer-sized odd
        # vocab with auto-tp would otherwise fail at shard_params.
        while tp > 1 and (
            self.model_cfg.num_kv_heads % tp != 0
            or self.model_cfg.vocab_size % tp != 0
        ):
            tp -= 1
        self.mesh = make_mesh(tp=tp, dp=cfg.dp, sp=cfg.sp, ep=cfg.ep)
        self.lock = threading.RLock()
        # Re-entrancy guard for mesh_ctx (per-thread): the jit cache keys
        # on the mesh-context STACK, so `with mesh:` nested inside another
        # `with mesh:` compiles a separate program from a single-level
        # entry with an identical signature (measured r04: warmup-compiled
        # sampler programs were recompiled inside the serving window).
        # Every engine jit call enters the mesh through mesh_ctx so the
        # ambient depth is exactly one, no matter how call paths compose.
        self._mesh_tls = threading.local()

        # Which kernels run (``self.kernels``), resolved ONCE here, before
        # anything is built on the device, and handed to every step program
        # as one argument. The attention reader, the state kernel and the
        # expert blocks are the code's own choice from the platform and the
        # model's shapes, and nothing outside the code can name one
        # (ops.kernels.choose_kernels: a kernel on a TPU where it has the
        # shapes, else XLA); the weight stream is an explicit request
        # (config field / env knob, default xla), and a weight stream asked
        # for that cannot be honoured is an error with the reason
        # (BackendRefused), never a quiet xla run under the kernel's name.
        # impl_info() reports what runs.
        from ..ops import kernels as kernel_rules

        ws = cfg.weight_stream or os.environ.get(
            "OPSAGENT_WEIGHT_STREAM", ""
        ) or "xla"
        if ws not in ("xla", "pallas-dma"):
            raise ValueError(
                f"weight_stream={ws!r}: expected 'xla' or 'pallas-dma'"
            )
        if ws == "pallas-dma" and cfg.quantize not in ("int8", "int4"):
            # The kernel streams NARROW storage types; full-precision
            # weights have nothing to dequantize in-register.
            raise BackendRefused(
                "weight_stream=pallas-dma needs quantize=int8|int4 "
                f"(got {cfg.quantize or 'none'!r})"
            )
        if ws == "pallas-dma" and tp > 1:
            # Row-parallel projections (wo, wd) would need a psum
            # epilogue around the shard_mapped kernel.
            raise BackendRefused(
                f"weight_stream=pallas-dma is single-shard only (tp={tp})"
            )
        platform = self.mesh.devices.flat[0].platform
        self.kernels = kernel_rules.choose_kernels(
            self.model_cfg, platform=platform, tp=tp, ep=cfg.ep,
            dtype=cfg.dtype, quantize=cfg.quantize,
            kv_quantize=cfg.kv_quantize,
            state_dtype=jnp.dtype(llama.STATE_DTYPE).name, weights=ws,
        )
        refused = kernel_rules.pallas_refusal(
            "pallas-stream", **kernel_rules.reader_shapes(
                self.model_cfg, tp=tp, dtype=cfg.dtype,
                kv_quantize=cfg.kv_quantize))
        # The choice never answers the kernel where pallas_refusal has a
        # reason, so this raises only where a test has put the kernel in
        # the choice's place. Interpret mode (the CPU tests) has no Mosaic
        # and none of its tiling limits; pages the kernel has no reader
        # for (int8 under pallas-stream) stay refused there too.
        if self.kernels.attn == "pallas-stream" and refused and (
            cfg.kv_quantize or not kernel_rules.pallas_interpret()
        ):
            raise BackendRefused(refused)
        log.info(
            "paged attention reader: %s on %s%s, weight stream: %s (tp=%d%s)",
            self.kernels.attn, platform,
            f" ({refused})" if self.kernels.attn == "xla" and refused else "",
            ws, tp,
            ", shard_map over tp"
            if self.kernels.attn == "pallas-stream" and tp > 1
            else "",
        )

        # The cache is made below in the form the state kernel chosen reads.
        if self.model_cfg.has_state:
            log.info("%s state: %s", self.model_cfg.state_mixer,
                     self.kernels.state)
            # What carries a sequence between steps, tiers or replicas as a
            # page chain alone would serve this model without its
            # recurrent state: refuse it here, by name, instead.
            why = ("a model with linear-attention layers or Mamba layers "
                   "(recurrent state)")
            refused = {
                f"tp={tp}": tp > 1,
                "weight_stream=pallas-dma": ws == "pallas-dma",
                "offload=True (the host tier, fleet page transfer and "
                "peer fault-in carry page chains only)": cfg.offload,
                "sp > 1 ring prefill": cfg.sp > 1,
            }
            for what, hit in refused.items():
                if hit:
                    raise BackendRefused(f"{what} is not supported for {why}")
        if self.model_cfg.expert_share:
            log.info("expert share: %s", self.kernels.experts)
        if cfg.kv_quantize and cfg.kv_quantize != "int8":
            raise ValueError(
                f"kv_quantize={cfg.kv_quantize!r}: only 'int8' is supported"
            )
        if cfg.quantize and cfg.quantize not in ("int8", "int4"):
            raise ValueError(
                f"quantize={cfg.quantize!r}: supported values are "
                f"'int8' (per-channel) and 'int4' (group-wise)"
            )
        key = jax.random.PRNGKey(cfg.seed)
        specs = llama.param_specs(self.model_cfg)
        if cfg.quantize:
            from ..models.quant import quantize_params, quantize_specs

            specs = quantize_specs(specs, mode=cfg.quantize)
        t_load = time.perf_counter()
        if cfg.quantize and params is None and not cfg.checkpoint:
            # Random + quantized (benchmarks, smoke runs): build the
            # narrow tree directly ON DEVICE, every leaf created with its
            # mesh sharding — a full-precision host-side init + quantize
            # takes tens of minutes at 8B, and a tree built whole on
            # device 0 and resharded afterwards cannot exist at widths
            # that only fit sharded.
            log.warning(
                "no checkpoint given: initializing RANDOM %s weights "
                "for %s", cfg.quantize, self.model_cfg.name,
            )
            self.params = llama.init_params_random_quantized(
                self.model_cfg, cfg.seed, dtype=cfg.dtype,
                mode=cfg.quantize,
                out_shardings=spec_tree_shardings(specs, self.mesh),
            )
        else:
            # With quantization, checkpoint weights must be loaded and
            # quantized on the HOST: the full-precision tree is the thing
            # that does not fit the chip (Llama-3-8B bf16 = 16 GB on a
            # 16 GB v5e). Only the int8 tree is device_put onto the mesh.
            from contextlib import nullcontext

            host = (
                jax.default_device(_host_cpu_device())
                if cfg.quantize and params is None else nullcontext()
            )
            with host:
                if params is None:
                    if cfg.checkpoint:
                        from ..models.loader import load_checkpoint

                        params = load_checkpoint(
                            cfg.checkpoint, self.model_cfg, cfg.dtype
                        )
                    else:
                        log.warning(
                            "no checkpoint given: initializing RANDOM "
                            "weights for %s", self.model_cfg.name,
                        )
                        params = llama.init_params(
                            self.model_cfg, key, dtype=cfg.dtype
                        )
                if cfg.quantize and not params_quantized:
                    params = quantize_params(params, mode=cfg.quantize)
                    log.info(
                        "weights quantized to %s (%s scales)",
                        cfg.quantize,
                        "per-output-channel" if cfg.quantize == "int8"
                        else "group-wise",
                    )
            # A source that made a patterned model's layers in order
            # holds them run by run: the layer scan wants them by period.
            params = llama.stack_layer_runs(self.model_cfg, params)
            self.params = shard_params(params, specs, self.mesh)
        # Block on the transfers so weights_load_s measures the actual
        # host->HBM move, not just the device_put enqueue.
        jax.block_until_ready(self.params)
        # /healthz "init" block: how this replica came up. warmup() adds
        # its wall time; the snapshot restore path stamps its source +
        # fingerprint after construction.
        self.init_stats: dict[str, Any] = {
            "weights_load_s": round(time.perf_counter() - t_load, 3),
            "warmup_s": 0.0,
            "restore_source": "",
            "snapshot_fingerprint": "",
            # Where compiled programs persist, and how warm that was
            # when this engine started (0 = every program compiles).
            "compile_cache_dir": self.compile_cache_dir or "",
            "compile_cache_entries_at_start": cache_entries,
        }
        # The page pool is created sharded as well: no leaf ever exists
        # whole on one device. Its pages are held in the form both the
        # page write and the page gather run in at this shard's kv heads
        # (ops.attention.page_form); outside the step programs a page is
        # [P, K, D] whatever is held (``cache_wire``: the split cache's
        # shapes, which the host tier and the snapshot manifest use).
        self.page_form = llama.cache_form(
            self.model_cfg, tp, self.kernels.attn)
        # Recurrent-state slots of a model with linear-attention layers:
        # live ones and the snapshot pool, one device array (a restore is
        # one copy between slots).
        live = snaps = 0
        if self.model_cfg.has_state:
            live = cfg.max_batch_size
            snaps = cfg.state_snapshots or 2 * live

        def make(form: str):
            return llama.make_cache(
                self.model_cfg, cfg.num_pages, cfg.page_size,
                dtype=cfg.dtype, kv_quantize=cfg.kv_quantize, form=form,
                state_slots=live + snaps, state_impl=self.kernels.state,
            )

        self.cache = jax.jit(
            lambda: make(self.page_form),
            out_shardings=spec_tree_shardings(
                llama.cache_specs(
                    self.model_cfg, kv_quantize=cfg.kv_quantize,
                    form=self.page_form, state_impl=self.kernels.state,
                ),
                self.mesh,
            ),
        )()
        self.cache_wire = jax.eval_shape(lambda: make("split"))
        if cfg.offload and "stats" in self.cache:
            raise BackendRefused(
                "offload=True is not supported for a model with an expert "
                "share: the host tier copies every leaf of the cache tree "
                "by page, and the share's device counters ride in that tree"
            )
        self.alloc = PageAllocator(
            cfg.num_pages, cfg.page_size, cfg.max_pages_per_seq,
            prefix_cache=cfg.prefix_cache,
            state_slots=live, state_snapshots=snaps,
        )
        self._state_copy_jit = jax.jit(
            llama.copy_state_slots, donate_argnames=("cache",)
        )
        if self.model_cfg.has_state:
            for part in ("state", "conv"):
                a = self.cache[part]
                obs.STATE_SLOT_BYTES.set(a.nbytes // a.shape[1], part=part)
        self._scan_layers = self.model_cfg.count_mixers("mamba")
        self._moe_stats_seen = np.zeros((len(llama.MOE_STATS),), np.uint32)
        self._snapshots_seen = [0, 0]    # taken, evicted: obs delta bases
        # Host-RAM offload tier: spills ride every trie eviction, restores
        # ride admission (begin_request). Parking APIs: park_chain (tool
        # windows), park_sequence (admission-pressure LRU).
        self.offload = None
        if cfg.offload and cfg.prefix_cache:
            from .offload import HostPagePool, OffloadManager, PageCopyEngine

            self.offload = OffloadManager(
                HostPagePool(
                    cfg.page_size,
                    capacity_bytes=cfg.host_pool_bytes or None,
                ),
                PageCopyEngine(
                    self.cache_wire,
                    mesh_ctx=self.mesh_ctx,
                    copy_pages=cfg.offload_copy_pages,
                ),
                cfg.page_size,
            )
            self.alloc.attach_host_pool(self.offload.pool)
            self.alloc.set_spill(self._spill_page)
        # Fleet-global KV fault-in client (fleet/pagestore.py). Wired by
        # the router (in-process) or run_engine_server (--join-fleet);
        # None = the peer-fetch tier is off and admission behaves as
        # before (trie -> host pool -> re-prefill).
        self._pagestore = None
        self._digests_truncated = False
        self.sequences: dict[int, Sequence] = {}
        self._evictions_seen = 0  # delta-sync base for the obs counter
        self._sample_key = jax.random.PRNGKey(cfg.seed + 1)
        # Under pallas-dma, leaves the kernel cannot take (stacked MoE
        # experts) stay on the XLA dequant inside the same program: say
        # how many take which path.
        self.weight_stream_leaves: dict[str, int] = {}
        if self.kernels.weights == "pallas-dma":
            self.weight_stream_leaves = llama.weight_stream_leaf_paths(
                self.params
            )
            log.info(
                "weight stream pallas-dma: quantized leaves by path %s",
                self.weight_stream_leaves,
            )
        # Goodput ledger: the static roofline cost model pricing every
        # dispatch from its batch composition (obs/attribution.py). Pure
        # host float math — nothing here is jitted or device-resident, so
        # the zero-post-warmup-compiles invariant is untouched.
        self.attr = obs.attribution.Attribution.for_engine(
            self.model_cfg, cfg, weight_stream=self.kernels.weights
        )
        obs.attribution.set_current(self.attr)

        mc, dt = self.model_cfg, cfg.dtype
        # The most tokens one mixed dispatch carries (decode lanes, forced
        # runs and chunks together), up to whole MXU passes of 128 rows:
        # where a mixed program's rows have more slots than HALF of this,
        # its matmuls run over the tokens packed to this width instead,
        # and over half of it in a tick that carries no more
        # (llama.mixed_step, llama.Pack.dense). Derived from the budget the
        # scheduler plans to; the planners below hold every dispatch to it.
        self.step_tokens = -(
            -max(cfg.max_step_tokens, cfg.max_batch_size) // 128) * 128

        # sp > 1: shard long-context prefill attention over the sp axis as
        # a ragged ring (each sequence masks by its own length inside every
        # ring step). Decode and the prefix-chunk path stay on paged ops.
        if cfg.sp > 1:
            from ..parallel.ring import make_ring_attention

            prefill_attn = make_ring_attention(self.mesh)
        else:
            prefill_attn = None

        # The two prefill programs never streamed their weights through the
        # kernel (it was compiled and compared at the step programs' shapes
        # alone) and do not start to.
        prefill_kernels = self.kernels._replace(weights="xla")

        def _prefill(params, tokens, lengths, cache, table):
            return llama.prefill(
                params, mc, tokens, lengths, cache, table, dtype=dt,
                prefill_attn=prefill_attn, kernels=prefill_kernels,
            )

        def _prefill_prefix(params, tokens, start, lengths, cache, table):
            return llama.prefill_with_prefix(
                params, mc, tokens, start, lengths, cache, table, dtype=dt,
                kernels=prefill_kernels, mesh=self.mesh,
            )

        def _decode_sample(
            params, tokens, lengths, cache, table, active,
            key, temps, top_k, top_p, mask, bias=None,
        ):
            """One fused decode+sample dispatch (one round trip, not two).
            ``bias`` [B, V] is the additive logit adjustment carrying
            OpenAI logit_bias and presence/frequency penalties."""
            logits, cache = llama.decode_step(
                params, mc, tokens, lengths, cache, table, active, dtype=dt,
                kernels=self.kernels, mesh=self.mesh,
            )
            if bias is not None:
                logits = logits + bias
            tok = sample(logits, key, temps, top_k, top_p, mask)
            return tok.astype(jnp.int32), cache

        def _decode_sample_lp(
            params, tokens, lengths, cache, table, active,
            key, temps, top_k, top_p, mask, bias=None,
        ):
            """Fused decode+sample that ALSO returns the sampled token's
            logprob and the top-20 alternatives (the OpenAI logprobs API
            caps top_logprobs at 20; a fixed width keeps the shape
            static). Used for rows whose request asked for logprobs.
            Logprobs reflect the post-bias distribution — the one actually
            sampled from."""
            logits, cache = llama.decode_step(
                params, mc, tokens, lengths, cache, table, active, dtype=dt,
                kernels=self.kernels, mesh=self.mesh,
            )
            if bias is not None:
                logits = logits + bias
            tok = sample(logits, key, temps, top_k, top_p, mask)
            lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            chosen = jnp.take_along_axis(
                lp, tok[:, None].astype(jnp.int32), axis=1
            )[:, 0]
            # Padded embedding vocab (e.g. Qwen): padded ids carry
            # arbitrary untrained logits and the tokenizer cannot render
            # them — keep them out of the top-20 alternatives.
            tv = min(self.tokenizer.vocab_size, mc.vocab_size)
            if tv < mc.vocab_size:
                lp = jnp.where(
                    jnp.arange(mc.vocab_size)[None, :] < tv, lp, -jnp.inf
                )
            tl, ti = jax.lax.top_k(lp, 20)
            return tok.astype(jnp.int32), chosen, ti.astype(jnp.int32), tl, cache

        def _mixed_sample(
            params, tokens, starts, qlens, cache, table,
            key, temps, top_k, top_p,
        ):
            """One fused mixed prefill+decode dispatch: ragged forward over
            decode rows (q_len=1) and prefill chunk rows (q_len=chunk) in
            the same batch, then one sample over every row's last-valid
            logits. Rows whose chunk does not finish its prompt get their
            sampled token discarded on host; q_len=0 rows are inert."""
            logits, cache = llama.mixed_step(
                params, mc, tokens, starts, qlens, cache, table, dtype=dt,
                kernels=self.kernels, mesh=self.mesh,
                step_tokens=self.step_tokens,
            )
            tok = sample(logits, key, temps, top_k, top_p, None)
            return tok.astype(jnp.int32), cache

        def _decode_pipeline(
            params, carry_tok, carry_at, carry_eos, key,
            override, ov_tok, ov_at, alive, budgets, cache, table,
            temps, top_k, top_p, greedy,
            fsm_mask=None, fsm_dest=None, carry_fsm=None, ov_fsm=None,
        ):
            from .decode_loop import decode_block_carry

            return decode_block_carry(
                params, mc, carry_tok, carry_at, carry_eos, key,
                override, ov_tok, ov_at, alive, budgets, cache, table,
                temps, top_k, top_p,
                jnp.int32(self.tokenizer.eos_id),
                jnp.int32(self.tokenizer.pad_id),
                fsm_mask=fsm_mask, fsm_dest=fsm_dest,
                carry_fsm=carry_fsm, ov_fsm=ov_fsm,
                n_steps=self.cfg.decode_block,
                greedy=greedy,
                dtype=dt,
                kernels=self.kernels,
                mesh=self.mesh,
            )

        self._prefill_jit = jax.jit(_prefill, donate_argnames=("cache",))
        self._prefill_prefix_jit = jax.jit(
            _prefill_prefix, donate_argnames=("cache",)
        )
        self._decode_sample_jit = jax.jit(
            _decode_sample, donate_argnames=("cache",)
        )
        self._decode_sample_lp_jit = jax.jit(
            _decode_sample_lp, donate_argnames=("cache",)
        )
        self._decode_pipeline_jit = jax.jit(
            _decode_pipeline,
            donate_argnames=("cache", "carry_tok", "carry_at", "carry_eos", "key"),
            static_argnames=("greedy",),
        )
        def _mixed_carry(
            params, tokens, use_carry, carry_tok, starts, qlens, emits,
            cache, table, key, temps, top_k, top_p,
            fsm_mask=None, fsm_dest=None, carry_fsm=None, ov_fsm=None,
        ):
            """The async variant of ``_mixed_sample``: decode lanes splice
            their input token from the previous dispatch's device-resident
            output (``carry_tok``), so tick t+1 dispatches before tick t's
            tokens ever reach the host (serving/async_runtime.py)."""
            from .decode_loop import mixed_step_carry

            return mixed_step_carry(
                params, mc, tokens, use_carry, carry_tok, starts, qlens,
                emits, cache, table, key, temps, top_k, top_p,
                dtype=dt, kernels=self.kernels, mesh=self.mesh,
                fsm_mask=fsm_mask, fsm_dest=fsm_dest,
                carry_fsm=carry_fsm, ov_fsm=ov_fsm,
                step_tokens=self.step_tokens,
            )

        self._mixed_sample_jit = jax.jit(
            _mixed_sample, donate_argnames=("cache",)
        )
        # carry_tok is deliberately NOT donated: it is pulled to host at
        # commit time, one dispatch after it fed the next tick.
        self._mixed_carry_jit = jax.jit(
            _mixed_carry, donate_argnames=("cache",)
        )
        self._sample_jit = jax.jit(sample)

        self._bias_buf = None  # reused host [B, V] logit-bias batch buffer
        self._fsm_dev: dict = {}  # id(fsm) -> (fsm, device mask, device dest)

        # -- pipelined decode state (see step_block) -------------------------
        B = cfg.max_batch_size
        self._lanes: list[int | None] = [None] * B   # lane -> seq_id
        self._lane_of: dict[int, int] = {}           # seq_id -> lane
        self._carry: tuple | None = None  # device (tok, at, eos, fsm, key)
        from collections import deque

        self._inflight: deque = deque()              # dispatched, unpulled
        self._inflight_steps: dict[int, int] = {}    # seq_id -> booked steps
        self._prefilling: dict[int, int] = {}        # seq_id -> tokens done

        # -- async mixed pipeline (see step_mixed_async) ---------------------
        from .async_runtime import AsyncMixedRuntime

        self._async = AsyncMixedRuntime(self)
        # Device-resident carries for the async mixed program: the
        # previous dispatch's sampled tokens / FSM states. Seeded by
        # warmup so every runtime dispatch sees program-output sharding
        # (the host-array variant compiles only once, inside warmup).
        self._async_carry = None
        self._async_fsm_carry = None
        # Constrained sequences already counted as ffwd-ineligible (the
        # fallback reason fires once per sequence, not once per tick).
        self._ffwd_noted: set[int] = set()
        # Wall-clock stamp of the last mixed dispatch's enqueue return,
        # shared by the sync and async tick paths: the gap to the next
        # dispatch is the opsagent_step_host_gap_seconds observable
        # (dispatch to dispatch: the wait for the device is inside it).
        self._mixed_gap_stamp: float | None = None
        # Device time of every dispatched step, learnt at its pull
        # (opsagent_step_device_seconds). A step's ticket number is also
        # its tick id: the flight "dispatch" event, the engine.dispatch /
        # wait / commit spans and the per-request span children carry it.
        self.step_clock = obs.StepClock()
        # Ticks the scheduler that drives this engine has counted (it bumps
        # this where it counts opsagent_ticks_total); _accept_token stamps
        # a sequence's first and newest token with it.
        self.sched_tick = 0
        # Seconds of what recurs a token (commit: account / stream /
        # stop_scan) or a row (plan: pages), summed here by the code that
        # times it and handed to obs.add_part once a stretch, by
        # _accepting and _building_arrays.
        self._token_sums: dict[str, float] = {}
        self._pages_s = 0.0

        if cfg.warmup:
            self.warmup()

    # Program groups compiled by warmup(). "full" is every serving program;
    # the narrower levels exist because XLA compile time is the scarce
    # resource under an external wall clock (VERDICT r2: full warmup's
    # cross-product of programs timed out the driver bench) — a benchmark
    # that only dispatches plain prefill + greedy block decode should only
    # pay for those.
    WARMUP_LEVELS: dict = {
        "bench": frozenset({"prefill", "sample", "decode_greedy"}),
        # The ragged-backend sweep drives the engine through sync
        # step_mixed only (admission chunks AND decode ticks both ride
        # the mixed program), so it needs exactly the mixed family — one
        # compile per mixed bucket, tracing through the RESOLVED
        # kernels, which is how each sweep cell's kernel gets compiled
        # before the timed window. Paying for the prefill/decode-block
        # cross-product per sweep cell would blow the stage budget.
        "bench-mixed": frozenset({"mixed"}),
        # "fsm" rides along: sessions workloads carry schema-constrained
        # rows since the grammar fast-forward bench, and a constrained
        # row's first block dispatch must not compile under load.
        "sessions": frozenset({
            "prefill", "prefill_prefix", "prefill_batched", "sample",
            "decode_greedy", "mixed", "mixed_async", "fsm", "ffwd",
            "offload",
        }),
        # "sessions" for traffic that never sends a response_format: no
        # grammar table is ever built, so neither the FSM variants of the
        # carry and decode-block programs nor the fast-forward appends can
        # be dispatched. At a model whose step program compiles in most of
        # a minute these are three whole compiles, one after the other.
        "sessions-free": frozenset({
            "prefill", "prefill_prefix", "prefill_batched", "sample",
            "decode_greedy", "mixed", "mixed_async", "offload",
        }),
        "full": frozenset({
            "prefill", "prefill_prefix", "prefill_batched", "sample",
            "decode_single", "logprobs", "decode_greedy", "decode_sampled",
            "fsm", "mixed", "mixed_async", "ffwd", "offload",
        }),
    }

    @property
    def pagestore(self):
        return self._pagestore

    @pagestore.setter
    def pagestore(self, client) -> None:
        if client is not None and self.model_cfg.has_state:
            raise BackendRefused(
                "the fleet page store (peer fault-in of page chains) is not "
                "supported for a model with linear-attention layers or Mamba "
                "layers: a page chain does not carry its recurrent state"
            )
        self._pagestore = client

    @contextlib.contextmanager
    def mesh_ctx(self):
        """Enter ``self.mesh`` at depth exactly one per thread: nested
        entries are no-ops. The jit cache keys on the mesh-context stack,
        so a nested `with mesh:` silently recompiles programs an outer
        single-level entry already compiled (see __init__'s _mesh_tls
        note)."""
        if getattr(self._mesh_tls, "active", False):
            yield
            return
        self._mesh_tls.active = True
        try:
            with self.mesh:
                yield
        finally:
            self._mesh_tls.active = False

    def impl_info(self) -> dict[str, Any]:
        """What actually runs: the device JAX put this engine on, the
        mesh, the attention and weight-stream backends, weight and KV
        quantization, and which FSM-table implementation serves
        constrained requests. Folded into ``/healthz`` and every bench
        result line's ``extra`` so rows and fleet snapshots are
        self-describing — the env knobs record what was ASKED for."""
        from .. import native

        dev = self.mesh.devices.flat[0]
        info: dict[str, Any] = {
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": len(jax.devices()),
            "mesh": {k: v for k, v in self.mesh.shape.items() if v > 1},
            "dtype": jnp.dtype(self.cfg.dtype).name,
            "attn_impl": self.kernels.attn,
            "weight_stream": self.kernels.weights,
            "quantize": self.cfg.quantize or "none",
            "kv_quantize": self.cfg.kv_quantize or "none",
            "kv_page_form": self.page_form,
            "fsm_impl": native.impl(),
            "step_rows": (
                f"packed:{self._step_rows(self.cfg.mixed_buckets[-1])}"
                if self.cfg.mixed_batching and llama.pack_widths(
                    self.cfg.max_batch_size * self.cfg.mixed_buckets[-1],
                    self.step_tokens) else "rows"
            ),
            # what the widest mixed program hands the page write: the
            # tick's packed tokens, or its rows' slots
            "kv_write": (
                self._kv_write(self.cfg.mixed_buckets[-1])[0]
                if self.cfg.mixed_batching else "rows"
            ),
        }
        if self.weight_stream_leaves:
            info["weight_stream_leaves"] = dict(self.weight_stream_leaves)
        if self.model_cfg.expert_share:
            info["moe_impl"] = self.kernels.experts
        if self.model_cfg.has_state:
            state = self.cache["state"]
            info["state_impl"] = self.kernels.state
            info["state_dtype"] = state.dtype.name
            info["state_slots"] = self.alloc.state_slots
            info["state_snapshots"] = self.alloc.state_snapshots
            # [linear layers, a slot's dims as held]: models.llama
            # state_slot_shape says which of its two layouts and why
            info["state_layout"] = [state.shape[0], *state.shape[2:]]
            info["state_slot_bytes"] = state.nbytes // state.shape[1]
            conv = self.cache["conv"]
            info["conv_slot_bytes"] = conv.nbytes // conv.shape[1]
            info["state_mixer"] = self.model_cfg.state_mixer
            if self.model_cfg.linear_attn is not None:
                info["lin_decay"] = self.model_cfg.linear_attn.decay
        return info

    def device_memory(self) -> list[dict[str, Any]]:
        """Allocator statistics of each device of this engine's mesh
        (``device.memory_stats()``: bytes in use, the peak since process
        start, the limit); empty where the backend reports none (CPU)."""
        out = []
        for dev in self.mesh.devices.flat:
            stats = dev.memory_stats()
            if stats:
                out.append({
                    "device": dev.id,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                    "bytes_limit": stats.get("bytes_limit"),
                })
        return out

    def _toolprompt_fsm_tables(self) -> tuple | None:
        """Device FSM tables of the agent's primary constraint (the ReAct
        ToolPrompt schema), which warmup pre-specializes the constrained
        programs for; None when they exceed the dense-table budget (the
        hosted-mask path then serves the schema and compiles nothing
        extra). Other schemas' table SHAPES compile on first use."""
        from .constrained import TOOLPROMPT_SCHEMA, json_constraint

        fsm = json_constraint(self.tokenizer, TOOLPROMPT_SCHEMA).fsm
        if fsm.dense_tables() is None:
            return None
        return self._fsm_device_tables(fsm)

    def _warmup_precompile_jobs(
        self, progs: frozenset
    ) -> list[tuple[str, Any]]:
        """(group, thunk) jobs for warmup's parallel pre-compile pass:
        each thunk is ``jit_fn.lower(args).compile()`` with the EXACT
        concrete arrays the sequential dispatch loop will pass (same
        avals, shardings, donation), so the executable it writes into the
        persistent compilation cache is the one the dispatch loop reads
        back. ``lower()`` only traces — nothing executes, no donated
        buffer is consumed, and ``self.cache`` is untouched.

        Only the straight-line program families are listed. The
        carry-chained variants (mixed_async, ffwd, pipeline second call)
        take device OUTPUTS as inputs — their argument shardings only
        exist after the first dispatch — so they stay sequential.
        """
        B = self.cfg.max_batch_size
        MaxP = self.alloc.table_width
        jobs: list[tuple[str, Any]] = []

        def add(group: str, fn, *args, **kw):
            jobs.append((group, lambda: fn.lower(*args, **kw).compile()))

        drop1 = jnp.full((1, MaxP), -1, jnp.int32)
        for bucket in self.cfg.prefill_buckets:
            toks = jnp.zeros((1, bucket), jnp.int32)
            ln = jnp.asarray([bucket], jnp.int32)
            if "prefill" in progs:
                add(
                    "prefill", self._prefill_jit,
                    self.params, toks, ln, self.cache, drop1,
                )
            if "prefill_prefix" in progs:
                add(
                    "prefill_prefix", self._prefill_prefix_jit,
                    self.params, toks, jnp.asarray([0], jnp.int32), ln,
                    self.cache, drop1,
                )
            if "prefill_batched" in progs:
                ceil = 1
                while ceil < self.cfg.prefill_batch:
                    ceil *= 2
                bp = 2
                while bp <= ceil:
                    add(
                        "prefill_batched", self._prefill_prefix_jit,
                        self.params,
                        jnp.zeros((bp, bucket), jnp.int32),
                        jnp.zeros((bp,), jnp.int32),
                        jnp.zeros((bp,), jnp.int32),
                        self.cache,
                        jnp.full((bp, MaxP), -1, jnp.int32),
                    )
                    bp *= 2
        dropB = jnp.full((B, MaxP), -1, jnp.int32)
        zi = jnp.zeros((B,), jnp.int32)
        zf = jnp.zeros((B,), jnp.float32)
        of = jnp.ones((B,), jnp.float32)
        inactive = jnp.zeros((B,), bool)
        # Lowering only consumes the key's aval, so peeling a split off
        # the live key WITHOUT advancing self._sample_key is safe here.
        sub = jax.random.split(self._sample_key)[1]
        if "mixed" in progs and self.cfg.mixed_batching:
            for sb in self.cfg.mixed_buckets:
                add(
                    "mixed", self._mixed_sample_jit,
                    self.params, jnp.zeros((B, sb), jnp.int32), zi, zi,
                    self.cache, dropB, sub, zf, zi, of,
                )
        biasB = None
        if "decode_single" in progs or "logprobs" in progs:
            biasB = jnp.zeros((B, self.model_cfg.vocab_size), jnp.float32)
        if "decode_single" in progs:
            for b in (None, biasB):
                add(
                    "decode_single", self._decode_sample_jit,
                    self.params, zi, zi, self.cache, dropB, inactive,
                    sub, zf, zi, of, None, *(() if b is None else (b,)),
                )
        if "logprobs" in progs:
            for b in (None, biasB):
                add(
                    "logprobs", self._decode_sample_lp_jit,
                    self.params, zi, zi, self.cache, dropB, inactive,
                    sub, zf, zi, of, None, b,
                )
        for greedy in (True, False):
            if ("decode_greedy" if greedy else "decode_sampled") not in progs:
                continue
            add(
                "decode_block", self._decode_pipeline_jit,
                self.params,
                jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
                jnp.zeros((B,), bool), sub,
                jnp.zeros((B,), bool), zi, zi, inactive, zi,
                self.cache, dropB, zf, zi, of,
                greedy=greedy,
                fsm_mask=None, fsm_dest=None,
                carry_fsm=jnp.zeros((B,), jnp.int32), ov_fsm=zi,
            )
        return jobs

    def warmup(self, level: str = "full") -> float:
        """Compile serving programs ahead of the first request: each
        prefill bucket (plain + prefix form), the pipelined decode block
        (greedy and sampled variants), the single-step decode, and the
        sampler. All warmup calls write through all-dropped page tables
        (-1 entries) with inactive rows, so device cache content and host
        page accounting are untouched. Returns wall seconds spent.

        ``level`` picks the program subset (WARMUP_LEVELS): "full" for
        serving, "sessions" for the concurrent-sessions path (batched
        admission + prefix prefill + greedy decode), "sessions-free" for
        the same path under free-text traffic only, "bench" for the
        minimal throughput-bench path (plain prefill + greedy decode).

        Combined with ``enable_compilation_cache`` this is one-time cost
        per (model, shape) config; subsequent engine starts replay the
        persistent cache instead of re-invoking XLA."""
        progs = self.WARMUP_LEVELS[level]
        t0 = time.perf_counter()
        B = self.cfg.max_batch_size
        MaxP = self.alloc.table_width
        # Compile-watchdog phase bracket: compiles inside count as
        # "warmup"; once any warmup completes, a compile during serving is
        # an anomaly (ring dump + opsagent_post_warmup_compiles).
        with obs.flight.warmup_phase(), self.lock, self.mesh_ctx():
            # Re-warming a LIVE engine: settle in-flight decode state first,
            # exactly like the legacy step path (warmup's throwaway carries
            # would otherwise desync lanes still referenced by pulls).
            self._async_settle()
            self._flush_and_invalidate()
            # Parallel pre-compile (OPSAGENT_WARMUP_PARALLEL, default on):
            # lower+compile the straight-line program families on a thread
            # pool FIRST, so XLA builds them concurrently; the sequential
            # dispatch loop below then reads each executable back from the
            # persistent compilation cache instead of compiling serially.
            # Gated on the cache being active — without it the AOT
            # executables are unreachable from the dispatch path and the
            # pass would compile everything twice. Worker-thread compiles
            # still count as "warmup" to the compile watchdog: the
            # warmup_phase bracket is a process-wide flag, not
            # thread-local. Sub-threshold programs (compile faster than
            # OPSAGENT_COMPILE_CACHE_MIN_S) recompile in the dispatch
            # loop; by definition that re-pay is cheap.
            par = os.environ.get("OPSAGENT_WARMUP_PARALLEL", "1") not in (
                "", "0",
            )
            if par and self.compile_cache_dir:
                jobs = self._warmup_precompile_jobs(progs)
                if len(jobs) > 1:
                    import concurrent.futures as _cf

                    def _run(item):
                        group, thunk = item
                        jt0 = time.perf_counter()
                        try:
                            with self.mesh_ctx():
                                thunk()
                        except Exception:  # noqa: BLE001
                            # Best-effort HERE only: the sequential pass
                            # below dispatches the same program and
                            # raises what the compiler raised.
                            log.exception(
                                "parallel warmup pre-compile failed for "
                                "%s; the sequential pass will raise it",
                                group,
                            )
                        return group, time.perf_counter() - jt0

                    workers = min(
                        len(jobs), max(2, (os.cpu_count() or 4) // 2), 8
                    )
                    groups: dict[str, float] = dict(
                        self.init_stats.get("warmup_groups", {})
                    )
                    with _cf.ThreadPoolExecutor(
                        max_workers=workers, thread_name_prefix="warmup"
                    ) as ex:
                        for group, secs in ex.map(_run, jobs):
                            groups[group] = round(
                                groups.get(group, 0.0) + secs, 3
                            )
                    self.init_stats["warmup_groups"] = groups
                    log.info(
                        "parallel warmup pre-compile: %d programs on %d "
                        "threads, per-group seconds %s",
                        len(jobs), workers, groups,
                    )
            drop1 = jnp.full((1, MaxP), -1, jnp.int32)
            logits = None
            for bucket in self.cfg.prefill_buckets:
                toks = jnp.zeros((1, bucket), jnp.int32)
                ln = jnp.asarray([bucket], jnp.int32)
                if "prefill" in progs:
                    logits, self.cache = self._prefill_jit(
                        self.params, toks, ln, self.cache, drop1
                    )
                if "prefill_prefix" in progs:
                    logits, self.cache = self._prefill_prefix_jit(
                        self.params, toks, jnp.asarray([0], jnp.int32), ln,
                        self.cache, drop1,
                    )
                # Batched-admission variants: every power of two up to the
                # PADDED ceiling (prefill_batch=6 pads to 8 at runtime), and
                # the sampler at the same widths (several same-bucket rows
                # can finish in one dispatch).
                if "prefill_batched" in progs:
                    ceil = 1
                    while ceil < self.cfg.prefill_batch:
                        ceil *= 2
                    bp = 2
                    while bp <= ceil:
                        lg, self.cache = self._prefill_prefix_jit(
                            self.params,
                            jnp.zeros((bp, bucket), jnp.int32),
                            jnp.zeros((bp,), jnp.int32),
                            jnp.zeros((bp,), jnp.int32),
                            self.cache,
                            jnp.full((bp, MaxP), -1, jnp.int32),
                        )
                        self._sample_one(lg, [])
                        bp *= 2
            if "sample" in progs and logits is not None:
                self._sample_one(logits, [])
            dropB = jnp.full((B, MaxP), -1, jnp.int32)
            zi = jnp.zeros((B,), jnp.int32)
            zf = jnp.zeros((B,), jnp.float32)
            of = jnp.ones((B,), jnp.float32)
            inactive = jnp.zeros((B,), bool)
            toks = None
            # Mixed prefill+decode programs: one per chunk bucket (the
            # query axis is the only shape that varies — decode-lane count
            # and chunk sizes within a bucket are DATA, not shape — so
            # this cross-product keeps mixed dispatches compile-free no
            # matter the batch composition). q_lens all zero: inert rows,
            # no KV writes, all-dropped tables.
            if "mixed" in progs and self.cfg.mixed_batching:
                for sb in self.cfg.mixed_buckets:
                    self._sample_key, sub = jax.random.split(self._sample_key)
                    toks, self.cache = self._mixed_sample_jit(
                        self.params,
                        jnp.zeros((B, sb), jnp.int32),
                        zi, zi,
                        self.cache,
                        dropB,
                        sub, zf, zi, of,
                    )
            # Carry-chained ASYNC mixed programs: per bucket, TWO chained
            # calls (the first sees fresh host carries, every later one
            # the previous dispatch's outputs — different input
            # shardings, hence two jit entries; see warm_pipeline's
            # note), with and without dense FSM tables when "fsm" is in
            # the level. The final carries are KEPT — runtime dispatches
            # always chain from program outputs, so the host-array
            # variant never recompiles inside the serving window.
            if (
                "mixed_async" in progs and self.cfg.mixed_batching
                and self.cfg.async_depth > 1
            ):
                a_carry = self._async_carry
                a_fsm = self._async_fsm_carry
                if a_carry is None:
                    a_carry = jnp.zeros((B,), jnp.int32)
                if a_fsm is None:
                    a_fsm = jnp.zeros((B,), jnp.int32)
                fsm_tabs: list[tuple] = [(None, None)]
                if "fsm" in progs:
                    tabs = self._toolprompt_fsm_tables()
                    if tabs is not None:
                        fsm_tabs.append(tabs)
                zb = jnp.zeros((B,), bool)
                for sb in self.cfg.mixed_buckets:
                    for fm, fd in fsm_tabs:
                        for _ in range(2):
                            self._sample_key, sub = jax.random.split(
                                self._sample_key
                            )
                            a_carry, self.cache, a_fsm = (
                                self._mixed_carry_jit(
                                    self.params,
                                    jnp.zeros((B, sb), jnp.int32),
                                    zb, a_carry, zi, zi, zb,
                                    self.cache, dropB,
                                    sub, zf, zi, of,
                                    fsm_mask=fm, fsm_dest=fd,
                                    carry_fsm=a_fsm, ov_fsm=zi,
                                )
                            )
                self._async_carry = a_carry
                self._async_fsm_carry = a_fsm
                toks = a_carry
            # Grammar fast-forward programs: the multi-token forced-run
            # append reuses _mixed_carry_jit with dense FSM tables and
            # FRESH host carries every call (the sync ffwd path never
            # chains device carries), so both the host-array and chained
            # variants must exist PER BUCKET. Warmed independently of
            # async_depth — depth-1 engines take this path too. At
            # depth>1 with "fsm" in the level the mixed_async block above
            # already compiled these entries; re-dispatching is a cache
            # hit, not a recompile.
            if (
                "ffwd" in progs and self.cfg.mixed_batching
                and self.cfg.grammar_ffwd
            ):
                tabs = self._toolprompt_fsm_tables()
                if tabs is not None:
                    fm, fd = tabs
                    zb = jnp.zeros((B,), bool)
                    for sb in self.cfg.mixed_buckets:
                        f_carry = jnp.zeros((B,), jnp.int32)
                        f_fsm = jnp.zeros((B,), jnp.int32)
                        for _ in range(2):
                            self._sample_key, sub = jax.random.split(
                                self._sample_key
                            )
                            f_carry, self.cache, f_fsm = (
                                self._mixed_carry_jit(
                                    self.params,
                                    jnp.zeros((B, sb), jnp.int32),
                                    zb, f_carry, zi, zi, zb,
                                    self.cache, dropB,
                                    sub, zf, zi, of,
                                    fsm_mask=fm, fsm_dest=fd,
                                    carry_fsm=f_fsm, ov_fsm=zi,
                                )
                            )
                            toks = f_carry
            if "decode_single" in progs:
                self._sample_key, sub = jax.random.split(self._sample_key)
                _, self.cache = self._decode_sample_jit(
                    self.params, zi, zi, self.cache, dropB, inactive,
                    sub, zf, zi, of, None,
                )
            # Bias / logprobs variants: the first logit_bias, penalty, or
            # logprobs request must not pay an XLA compile under the
            # engine lock.
            biasB = None
            if "decode_single" in progs or "logprobs" in progs:
                biasB = jnp.zeros(
                    (B, self.model_cfg.vocab_size), jnp.float32
                )
            if "decode_single" in progs:
                self._sample_key, sub = jax.random.split(self._sample_key)
                _, self.cache = self._decode_sample_jit(
                    self.params, zi, zi, self.cache, dropB, inactive,
                    sub, zf, zi, of, None, biasB,
                )
            if "logprobs" in progs:
                for b in (None, biasB):
                    self._sample_key, sub = jax.random.split(self._sample_key)
                    _, _, _, _, self.cache = self._decode_sample_lp_jit(
                        self.params, zi, zi, self.cache, dropB, inactive,
                        sub, zf, zi, of, None, b,
                    )
            greedy_variants = [
                g for g in (True, False)
                if ("decode_greedy" if g else "decode_sampled") in progs
            ]
            def warm_pipeline(greedy: bool, fm=None, fd=None):
                """TWO chained calls mirroring step_block's exact argument
                structure (carry_fsm/ov_fsm always passed): the first
                dispatch sees fresh host arrays, every later one sees the
                previous dispatch's OUTPUTS as carries — different input
                shardings, hence a second jit cache entry. Both must
                compile here or the second real block pays XLA inside the
                serving window."""
                self._sample_key, sub = jax.random.split(self._sample_key)
                carry = (
                    jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
                    jnp.zeros((B,), bool), jnp.zeros((B,), jnp.int32), sub,
                )
                toks = None
                for _ in range(2):
                    c_tok, c_at, c_eos, c_fsm, c_key = carry
                    toks, self.cache, carry = self._decode_pipeline_jit(
                        self.params,
                        c_tok, c_at, c_eos, c_key,
                        jnp.zeros((B,), bool), zi, zi, inactive, zi,
                        self.cache, dropB, zf, zi, of,
                        greedy=greedy,
                        fsm_mask=fm, fsm_dest=fd,
                        carry_fsm=c_fsm, ov_fsm=zi,
                    )
                return toks

            for greedy in greedy_variants:
                toks = warm_pipeline(greedy)
            # Device-FSM decode variant, pre-specialized for the agent's
            # primary constraint (the ReAct ToolPrompt schema): the first
            # constrained request must not pay the dense-table build plus
            # an XLA compile under the engine lock. Other schemas' table
            # SHAPES still compile on first use (unknowable here).
            if "fsm" in progs:
                tabs = self._toolprompt_fsm_tables()
                if tabs is not None:
                    for greedy in (True, False):
                        toks = warm_pipeline(greedy, *tabs)
            # Offload-tier copy programs (gather + scatter per bucket):
            # the restore path runs inside admission, where an XLA compile
            # would be a post-warmup anomaly. warm() rewrites page 0 with
            # its own content — state-preserving like every warmup call.
            if "offload" in progs and self.offload is not None:
                self.cache = self.offload.copier.warm(self.cache)
            # The state-slot copy (a snapshot restored at admission): one
            # slot to nowhere, which copies nothing.
            if self.alloc.state_slots:
                one = jnp.zeros((1,), jnp.int32)
                self.cache = self._state_copy_jit(self.cache, one, one - 1)
            # ... and the copy a /metrics scrape reads the expert share's
            # accumulators from.
            self.sync_device_counters()
            self._carry = None  # warmup carries are throwaways
            # A real device->host pull: on async backends block_until_ready
            # returns immediately, and the point of warmup is that the
            # FIRST request finds an idle, fully-compiled device.
            if toks is not None:
                np.asarray(toks)
            elif logits is not None:
                np.asarray(logits)
        dt = time.perf_counter() - t0
        log.info("engine warmup[%s]: programs compiled in %.1f s", level, dt)
        get_perf_stats().record_metric("engine.warmup", dt * 1e3, "ms")
        obs.flight.record("warmup", level=level, seconds=round(dt, 3))
        self.init_stats["warmup_s"] = round(
            self.init_stats.get("warmup_s", 0.0) + dt, 3
        )
        return dt

    # -- snapshot/restore (serving/snapshot) --------------------------------
    def snapshot(self, path: str) -> dict:
        """Write this engine's restart snapshot (weights in device
        layout, the persistent compile cache, the paged-KV plan) under
        ``path``. Snapshot a WARMED engine under
        ``OPSAGENT_COMPILE_CACHE_MIN_S=0`` — cache entries are written at
        compile time, so programs compiled before the threshold was
        lowered are not in the artifact. Returns the manifest dict."""
        from .snapshot.writer import write_snapshot

        if self.model_cfg.has_state:
            raise BackendRefused(
                "Engine.snapshot (snapshot/writer.py) is not supported for "
                "a model with linear-attention layers or Mamba layers: its "
                "paged-KV plan has no place for the recurrent state"
            )
        with self.lock:
            return write_snapshot(self, path)

    @classmethod
    def from_snapshot(
        cls,
        path: str,
        warmup: bool | str | None = None,
        tokenizer: Tokenizer | None = None,
    ) -> "Engine":
        """Restore an engine from a snapshot directory: weight leaves are
        mmap'd straight into ``device_put`` with the re-derived shardings
        (no loader round trip) and the compile cache is pre-seeded so the
        warmup sweep (``warmup=True`` for "full", or a WARMUP_LEVELS
        name) is a cache-hit replay. Refuses mismatched fingerprints,
        device counts, and leaf orders with a ``SnapshotError``."""
        from .snapshot.restore import restore_engine

        return restore_engine(path, warmup=warmup, tokenizer=tokenizer)

    # -- bucketing ---------------------------------------------------------
    def _bucket(self, n: int) -> int:
        """Smallest prefill bucket holding n tokens. Tails longer than the
        largest bucket are CHUNKED through it by add_request, so bucket
        choice never rejects a prompt — only the page budget
        (max_pages_per_seq) bounds prompt length."""
        for b in self.cfg.prefill_buckets:
            if n <= b:
                return b
        return self.cfg.prefill_buckets[-1]

    # -- request lifecycle -------------------------------------------------
    def add_request(
        self,
        prompt_ids: list[int],
        sampling: SamplingParams | None = None,
        mask_fn: Callable[[list[int]], np.ndarray] | None = None,
        stream: Callable[[int], None] | None = None,
        trace: Any = None,
    ) -> int:
        """Admit a request synchronously: allocate pages, run the whole
        prefill, sample the first token. Returns the sequence id. Raises
        OutOfPages when full.

        This is ``begin_request`` + ``prefill_step`` until done — the
        scheduler uses those directly so prefill CHUNKS interleave with
        decode blocks instead of stalling every running stream for the
        whole admission (VERDICT round-1 weak #7)."""
        with self.lock:
            seq_id = self.begin_request(
                prompt_ids, sampling, mask_fn, stream, trace=trace
            )
            while not self.prefill_step(seq_id):
                pass
            return seq_id

    def begin_request(
        self,
        prompt_ids: list[int],
        sampling: SamplingParams | None = None,
        mask_fn: Callable[[list[int]], np.ndarray] | None = None,
        stream: Callable[[int], None] | None = None,
        trace: Any = None,
        expect_restore: bool = False,
    ) -> int:
        """Stage 1 of admission: allocate pages (reusing any cached prefix)
        and register the sequence in the 'prefilling' state. No model pass
        runs here, but it is not free: the trie match hashes every page of
        the prompt, a model with recurrent state enqueues one ``state_copy``
        dispatch to restore a snapshot, and the offload tier copies pages
        back from the host. Follow with ``prefill_step`` calls until it
        returns True; only then does the sequence decode.

        The parts of ``admit`` here (docs/observability.md "Tick phases"):
        ``fault_in``, ``match``, ``alloc``, ``host_restore``, ``register``
        and ``account``; the ``state_copy`` is a ``dispatch`` of its own."""
        sampling = sampling or SamplingParams()
        n = len(prompt_ids)
        if n == 0:
            raise InvalidRequest("empty prompt")
        if n >= self.model_cfg.max_position:
            # Positions past the model's rope window produce degenerate
            # attention (e.g. the DeepSeek presets clamp to the native
            # pre-YaRN window); fail the request loudly instead.
            raise InvalidRequest(
                f"prompt of {n} tokens exceeds the model's "
                f"{self.model_cfg.max_position}-position context window"
            )
        if n + sampling.max_tokens > self.model_cfg.max_position:
            # Decode must not run positions past the window either: clamp
            # the generation budget (OpenAI-style context-limit behavior —
            # the request finishes with reason "length" at the window).
            from dataclasses import replace as _dc_replace

            sampling = _dc_replace(
                sampling, max_tokens=self.model_cfg.max_position - n
            )
        # Fleet-global KV tier: if the prompt misses locally (trie AND
        # host pool), fault the missing chain in from a peer BEFORE
        # taking the engine lock — the fetch is network I/O and must not
        # stall running decode. Landed pages are host-pool entries under
        # the same chain keys, so the locked restore below picks them up
        # through the unchanged _restore_from_host path. Never raises;
        # a miss/failure just means the prefill loop covers the tokens.
        with obs.phase("admit", part="fault_in"):
            faulted_pages = self.fault_in_prefix(
                prompt_ids,
                request_id=obs.flight.request_id_of(trace) or "",
            )
        with self.lock:
            if self.offload is not None:
                # Land pending spills first: a page parked during the
                # previous tick must be matchable by THIS admission.
                with obs.phase("admit", part="host_restore"):
                    self.offload.flush()
            # Prefix cache: reuse full pages of the prompt MINUS its last
            # token (at least one tail token must be prefilled to produce
            # the next-token logits).
            snapshot = -1
            with obs.phase("admit", part="match"):
                if self.alloc.state_slots:
                    # Recurrent state: a page chain is reusable only up to
                    # a node that holds a state snapshot; restoring is one
                    # device copy into the sequence's slot, enqueued here,
                    # before any step that could write either slot.
                    prefix_pages, snapshot, full = (
                        self.alloc.match_prefix_state(prompt_ids[: n - 1]))
                    given_up = (
                        (full - len(prefix_pages)) * self.cfg.page_size)
                    obs.STATE_PROMPT_TOKENS.inc(n)
                    if given_up:
                        obs.STATE_UNMATCHED_TOKENS.inc(given_up)
                else:
                    prefix_pages = self.alloc.match_prefix(
                        prompt_ids[: n - 1])
                matched = len(prefix_pages) * self.cfg.page_size
            with obs.phase("admit", part="alloc"):
                seq_id = self.alloc.allocate(n, prefix_pages=prefix_pages)
            if snapshot >= 0:
                self._copy_state([snapshot], [self.alloc.state_slot(seq_id)])
                obs.STATE_SNAPSHOTS.inc(event="restored")
                obs.STATE_RESTORED_TOKENS.inc(matched)
            with obs.phase("admit", part="host_restore"):
                restored = self._restore_from_host(
                    seq_id, prompt_ids, n, len(prefix_pages), matched
                )
                if faulted_pages and restored and self.offload is not None:
                    self.offload.note_remote_hit(
                        min(restored, faulted_pages * self.cfg.page_size)
                    )
            if expect_restore and matched + restored < (
                (n - 1) // self.cfg.page_size
            ) * self.cfg.page_size:
                # A PARKED session came back and its host-pool pages were
                # gone (LRU-dropped under the byte bound, or a failed
                # restore): correctness falls back to re-prefill, but
                # silently eating that cost is how fidelity regressions
                # hide — ring-dump it.
                obs.OFFLOAD_RESTORE_FALLBACKS.inc()
                obs.flight.anomaly(
                    "restore_reprefill", seq_id=seq_id, prompt_tokens=n,
                    prefix_hit_tokens=matched, restored_tokens=restored,
                    request_id=obs.flight.request_id_of(trace),
                )
            with obs.phase("admit", part="register"):
                seq = Sequence(
                    seq_id, n, prompt_ids=list(prompt_ids),
                    params=sampling, mask_fn=mask_fn, stream=stream,
                    trace=trace,
                )
                self.sequences[seq_id] = seq
                self._prefilling[seq_id] = matched + restored
            with obs.phase("admit", part="account"):
                if matched:
                    get_perf_stats().record_metric(
                        "engine.prefix_hit_tokens", matched, "tok"
                    )
                    obs.PREFIX_HIT_TOKENS.inc(matched)
                obs.flight.record(
                    "admission", seq_id=seq_id, prompt_tokens=n,
                    prefix_hit_tokens=matched, restored_tokens=restored,
                    request_id=obs.flight.request_id_of(trace),
                )
                self._observe_occupancy()
            return seq_id

    def _copy_state(self, src: list[int], dst: list[int]) -> None:
        """Copy recurrent-state slots ``src`` to ``dst`` on the device: a
        dispatch of its own on the step clock (program ``state_copy``),
        ordered with the steps like any other. Under the engine lock."""
        ticket = self.step_clock.enqueue()
        with obs.phase("dispatch", tick=ticket[0]), self.mesh_ctx():
            with obs.phase("dispatch", part="place"):
                src_d = jnp.asarray(src, jnp.int32)
                dst_d = jnp.asarray(dst, jnp.int32)
            with obs.phase("dispatch", part="call"), \
                    annotate("engine.state_copy"):
                self.cache = self._state_copy_jit(self.cache, src_d, dst_d)
        obs.flight.record(
            "dispatch", op="state_copy", slots=len(src), tick=ticket[0])

    def sync_device_counters(self) -> None:
        """Read the expert share's accumulators off the device into
        ``opsagent_moe_share_total``, at a scrape. Under the engine lock
        only a copy is enqueued behind the steps in flight (a step donates
        the cache itself); the wait for it is outside the lock. The
        accumulators are uint32 and wrap, so a delta is taken modulo 2**32:
        right as long as scrapes are less than 2**32 assignments apart
        (days of serving) and one at a time (the ``/metrics`` handler, on
        the event loop)."""
        with self.lock, self.mesh_ctx():
            if "stats" not in self.cache:
                return
            stats = jnp.copy(self.cache["stats"])
        now = np.asarray(stats)
        with self.lock:
            delta = now - self._moe_stats_seen      # uint32: modulo 2**32
            self._moe_stats_seen = now
        for what, d in zip(llama.MOE_STATS, delta):
            if d:
                obs.MOE_SHARE.inc(float(d), what=what)

    def _restore_from_host(
        self, seq_id: int, prompt_ids: list[int], n: int,
        shared_pages: int, matched: int,
    ) -> int:
        """Restore-instead-of-reprefill: pages of this prompt beyond the
        HBM trie hit that the host pool still holds are copied back into
        the freshly-allocated pages and re-registered into the trie
        (``promote_prefix``), so the prefill loop starts AFTER them and
        partial restores become trie hits for concurrent admissions.
        Returns restored token count (0 on miss or any failure — the
        caller's chunked prefill then covers those tokens, the tier-1
        behavior)."""
        if self.offload is None:
            return 0
        seq_pages = self.alloc.pages_of(seq_id)
        entries = self.offload.pool.match(
            prompt_ids[: n - 1],
            start_page=shared_pages,
            max_pages=len(seq_pages) - shared_pages,
        )
        if not entries:
            return 0
        dst = seq_pages[shared_pages : shared_pages + len(entries)]
        try:
            def _keep(c):
                self.cache = c

            self.cache, restored = self.offload.restore(
                self.cache, dst, entries, seq_id=seq_id, on_update=_keep
            )
        except Exception:  # noqa: BLE001 - fall back to re-prefill
            log.exception(
                "host->device KV restore failed; re-prefilling "
                "(pages will be overwritten by the prefill chunks)"
            )
            return 0
        if restored:
            self.alloc.promote_prefix(
                seq_id, prompt_ids[: matched + restored]
            )
            get_perf_stats().record_metric(
                "engine.restore_tokens", restored, "tok"
            )
        return restored

    def next_prefill_bucket(self, seq_id: int) -> int:
        """Bucket the given admitting sequence's NEXT chunk compiles into —
        the scheduler's grouping key for batched admission."""
        with self.lock:
            seq = self.sequences[seq_id]
            done = self._prefilling[seq_id]
            return self._bucket(self.alloc.clamp_chunk(
                seq_id, done, seq.prompt_len,
                min(seq.prompt_len - done, self.cfg.prefill_buckets[-1])
            ))

    def prefill_batch(self, seq_ids: list[int]) -> dict[int, bool]:
        """Run ONE prefill chunk for EACH given admitting sequence in a
        single batched dispatch (same-bucket grouping is the caller's job;
        smaller chunks ride as padding). Under concurrent admissions
        (BASELINE config 5) this divides per-session TTFT by the batch
        width instead of prefilling one session per scheduler tick.

        Rows are padded to a power-of-two batch so XLA compiles a handful
        of (batch, bucket) variants, with padding rows writing through
        dropped (-1) page tables. Every row runs the prefix-attention
        program (start = tokens already prefilled; 0 for fresh prompts —
        same math, one code path batches mixed admission states).

        Returns {seq_id: fully_prefilled | Exception}: row-local failures
        (a raising stream callback or mask_fn on the first token) clean up
        and fail ONLY their own row — per-request isolation matches the
        decode path's one-bad-apple contract. A failed DISPATCH cleans up
        every batched sequence (pages freed, Sequence dropped) before the
        exception propagates."""
        with self.lock:
            self._async_settle()
            self._mixed_gap_stamp = None  # see step(): gap continuity ends
            try:
                with obs.phase("plan", part="chunks"):
                    seqs = [self.sequences[s] for s in seq_ids]
                    dones = [self._prefilling[s] for s in seq_ids]
                    chunks = [
                        self.alloc.clamp_chunk(
                            sid, d, seq.prompt_len,
                            min(seq.prompt_len - d,
                                self.cfg.prefill_buckets[-1]))
                        for sid, seq, d in zip(seq_ids, seqs, dones)
                    ]
                    bucket = self._bucket(max(chunks))
                with self._building_arrays():
                    Bp = 1
                    while Bp < len(seq_ids):
                        Bp *= 2
                    tokens = np.full(
                        (Bp, bucket), self.tokenizer.pad_id, np.int32
                    )
                    starts = np.zeros((Bp,), np.int32)
                    lens = np.zeros((Bp,), np.int32)
                    tables = np.full(
                        (Bp, self.alloc.table_width), -1, np.int32
                    )
                    for i, (sid, seq, d, c) in enumerate(
                        zip(seq_ids, seqs, dones, chunks)
                    ):
                        tokens[i, :c] = seq.prompt_ids[d : d + c]
                        starts[i] = d
                        lens[i] = c
                        tables[i] = self._pass_row(sid, d, c)
                ticket = self.step_clock.enqueue()
                with obs.phase("dispatch", tick=ticket[0]), self.mesh_ctx():
                    with obs.phase("dispatch", part="place"):
                        tokens_d, starts_d, lens_d, tables_d = (
                            jnp.asarray(a)
                            for a in (tokens, starts, lens, tables))
                    with obs.phase("dispatch", part="call"), \
                            annotate("engine.prefill_chunk"):
                        logits, self.cache = self._prefill_prefix_jit(
                            self.params, tokens_d, starts_d, lens_d,
                            self.cache, tables_d,
                        )
                perf = get_perf_stats()
                with obs.phase("plan", part="account"):
                    perf.record_metric(
                        "engine.prefill_tokens", int(sum(chunks)), "tok"
                    )
                    obs.PREFILL_TOKENS.inc(int(sum(chunks)))
                    self._count_scan(int(sum(chunks)), Bp * bucket)
                    obs.flight.record(
                        "dispatch", op="prefill_batch",
                        seq_ids=list(seq_ids),
                        bucket=bucket, rows=len(seq_ids),
                        prefill_tokens=int(sum(chunks)), tick=ticket[0],
                    )
                    self.attr.dispatch(
                        "prefill_batch",
                        q_tokens=int(sum(chunks)),
                        kv_read_tokens=int(
                            sum(d + c for d, c in zip(dones, chunks))
                        ),
                        kv_write_tokens=int(sum(chunks)),
                        attn_q_ctx=int(sum(
                            obs.attribution.prefill_attn_positions(d, c)
                            for d, c in zip(dones, chunks)
                        )),
                    )
                out: dict[int, Any] = {}
                finished_rows = [
                    i for i, (seq, d, c) in enumerate(zip(seqs, dones, chunks))
                    if d + c >= seq.prompt_len
                ]
                # Pre-screen constrained rows: a raising mask_fn must fail
                # only its own row, and _sample_one batches every finished
                # row's masks in one call (mask_fns are pure, so the
                # screening call duplicates no state).
                bad: dict[int, Exception] = {}
                for i in finished_rows:
                    if seqs[i].mask_fn is None:
                        continue
                    try:
                        seqs[i].mask_fn(seqs[i].tokens)
                    except Exception as e:  # noqa: BLE001
                        bad[i] = e
                finished_rows = [i for i in finished_rows if i not in bad]
                first_toks = None
                if finished_rows:
                    # Sample the FULL padded batch and index on host: a
                    # device gather of `finished_rows` would specialize
                    # sample/gather programs on every distinct finished
                    # count (dozens of tiny compiles, all inside the
                    # serving window). Bp is already the program's
                    # padded row bucket; padding rows sample greedily
                    # into a discarded slot.
                    fset = set(finished_rows)
                    row_seqs: list[Any] = [
                        seqs[i] if i in fset else None
                        for i in range(len(seq_ids))
                    ] + [None] * (Bp - len(seq_ids))
                    first_toks = self._sample_one(
                        logits, row_seqs, ("prefill_chunk", bucket, ticket)
                    )
                with self._accepting(tick=ticket[0]):
                    for i, (sid, seq, d, c) in enumerate(
                        zip(seq_ids, seqs, dones, chunks)
                    ):
                        if i in bad:
                            self._drop_admission(sid)
                            out[sid] = bad[i]
                            continue
                        if d + c < seq.prompt_len:
                            self._prefilling[sid] = d + c
                            out[sid] = False
                            continue
                        del self._prefilling[sid]
                        token = int(first_toks[i])
                        seq.ttft_s = time.perf_counter() - seq.started_s
                        perf.record_metric(
                            "engine.ttft", seq.ttft_s * 1e3, "ms"
                        )
                        self._first_token_obs(seq)
                        try:
                            self._accept_token(seq, token)
                        except Exception as e:  # noqa: BLE001 - stream cb
                            self._drop_admission(sid)
                            out[sid] = e
                            continue
                        out[sid] = True
                with obs.phase("commit", part="account"):
                    self._observe_occupancy()
                return out
            except Exception:
                for sid in seq_ids:
                    self._drop_admission(sid)
                raise

    def _summed(self, part: str, t0: float) -> None:
        """``perf_counter() - t0`` more of ``commit``'s summed ``part``."""
        sums = self._token_sums
        sums[part] = sums.get(part, 0.0) + time.perf_counter() - t0

    @contextlib.contextmanager
    def _accepting(self, **ids):
        """The ``accept`` part of ``commit``. What ``_accept_token`` and
        the span children summed a token leaves it as parts of their own
        (``account``, ``stream``, ``stop_scan``): one ``obs.add_part``
        each, not one a token."""
        with obs.phase("commit", part="accept", **ids):
            try:
                yield
            finally:
                for part, seconds in self._token_sums.items():
                    obs.add_part("commit", part, seconds)
                self._token_sums.clear()

    @contextlib.contextmanager
    def _building_arrays(self):
        """The ``arrays`` part of ``plan``; the allocator's share that
        ``_pass_row`` summed a row leaves it as ``pages``."""
        with obs.phase("plan", part="arrays"):
            try:
                yield
            finally:
                obs.add_part("plan", "pages", self._pages_s)
                self._pages_s = 0.0

    def _pass_row(
        self, seq_id: int, start: int, q: int, each_token: bool = False
    ) -> np.ndarray:
        """The sequence's row of a step program's table for a pass (or,
        with ``each_token``, ``q`` one-token passes) that takes it from
        ``start`` tokens to ``start + q``: its pages and, for a model with
        recurrent state, its slots, with the pass noted for the snapshot's
        bookkeeping (``PageAllocator.note_pass``). Once a row of every
        plan: summed for ``plan``'s ``pages`` part (``_building_arrays``)."""
        t0 = time.perf_counter()
        self.alloc.note_pass(seq_id, start, start + q, each_token)
        row = self.alloc.page_table_row(seq_id)
        self._pages_s += time.perf_counter() - t0
        return row

    def _drop_admission(self, seq_id: int) -> None:
        """Clean one failed admission: pages freed, host state dropped."""
        self.sequences.pop(seq_id, None)
        self._prefilling.pop(seq_id, None)
        self.alloc.free(seq_id)

    def prefill_step(self, seq_id: int) -> bool:
        """Stage 2 of admission: run ONE bucket-sized prefill chunk,
        attending over all cache content before it (prefix pages plus
        previously prefilled chunks). Returns True when the prompt is fully
        prefilled — at which point the first token has been sampled and the
        sequence is decodable. Chunking keeps admission independent of
        prefix-cache state AND lets the scheduler slot decode blocks
        between chunks of a long prompt.

        On failure the sequence is cleaned up (pages freed, Sequence
        dropped) before the exception propagates: the scheduler only ever
        holds seq_ids whose state is live."""
        with self.lock:
            self._async_settle()
            self._mixed_gap_stamp = None  # see step(): gap continuity ends
            seq = self.sequences[seq_id]
            done = self._prefilling[seq_id]
            n = seq.prompt_len
            try:
                chunk = self.alloc.clamp_chunk(
                    seq_id, done, n,
                    min(n - done, self.cfg.prefill_buckets[-1]))
                table = jnp.asarray(
                    self._pass_row(seq_id, done, chunk)[None, :]
                )
                bucket = self._bucket(chunk)
                tokens = np.full((1, bucket), self.tokenizer.pad_id, np.int32)
                tokens[0, :chunk] = seq.prompt_ids[done:done + chunk]
                ticket = self.step_clock.enqueue()
                with obs.phase("dispatch", tick=ticket[0]), self.mesh_ctx():
                    with obs.phase("dispatch", part="place"):
                        tokens_d = jnp.asarray(tokens)
                        done_d = jnp.asarray([done], jnp.int32)
                        chunk_d = jnp.asarray([chunk], jnp.int32)
                    with obs.phase("dispatch", part="call"), \
                            annotate("engine.prefill_chunk"):
                        if done:
                            logits, self.cache = self._prefill_prefix_jit(
                                self.params, tokens_d, done_d, chunk_d,
                                self.cache, table,
                            )
                        else:
                            logits, self.cache = self._prefill_jit(
                                self.params, tokens_d, chunk_d, self.cache,
                                table,
                            )
                done += chunk
                perf = get_perf_stats()
                self._count_scan(chunk, bucket)
                with obs.phase("plan", part="account"):
                    perf.record_metric("engine.prefill_tokens", chunk, "tok")
                    obs.PREFILL_TOKENS.inc(chunk)
                    obs.flight.record(
                        "dispatch", op="prefill_chunk", seq_id=seq_id,
                        bucket=bucket, prefill_tokens=chunk,
                        prompt_done=done, prompt_total=n, tick=ticket[0],
                    )
                    self.attr.dispatch(
                        "prefill_chunk",
                        q_tokens=chunk,
                        kv_read_tokens=done,  # done already includes chunk
                        kv_write_tokens=chunk,
                        attn_q_ctx=obs.attribution.prefill_attn_positions(
                            done - chunk, chunk
                        ),
                    )
                if done < n:
                    self._prefilling[seq_id] = done
                    return False
                del self._prefilling[seq_id]
                token = int(self._sample_one(
                    logits, [seq], ("prefill_chunk", bucket, ticket)
                )[0])
                with self._accepting(tick=ticket[0]):
                    seq.ttft_s = time.perf_counter() - seq.started_s
                    perf.record_metric("engine.ttft", seq.ttft_s * 1e3, "ms")
                    self._first_token_obs(seq)
                    self._accept_token(seq, token)
                with obs.phase("commit", part="account"):
                    self._observe_occupancy()
                return True
            except Exception:
                # Failed admissions (prefill OOM, raising mask_fn, a raising
                # stream callback on the first token, ...) must not leak
                # pages or a stale Sequence.
                self.sequences.pop(seq_id, None)
                self._prefilling.pop(seq_id, None)
                self.alloc.free(seq_id)
                raise

    # -- mixed prefill+decode step -------------------------------------------
    def _mixed_bucket(self, n: int) -> int:
        """Smallest mixed-chunk bucket holding n query rows."""
        for b in self.cfg.mixed_buckets:
            if n <= b:
                return b
        return self.cfg.mixed_buckets[-1]

    def _step_rows(self, S: int, real: int | None = None) -> int:
        """Rows the matmuls of the mixed program of bucket ``S`` run over
        in a tick of ``real`` tokens (None: at most): its slots, or the
        packed width where it packs, or the narrow width where it packs
        and the tick carries no more (``llama.pack_widths``)."""
        slots = self.cfg.max_batch_size * S
        widths = llama.pack_widths(slots, self.step_tokens)
        if widths is None:
            return slots
        T, narrow = widths
        return narrow if real is not None and real <= narrow else T

    def _kv_write(self, S: int) -> tuple[str, int]:
        """``llama.kv_write_form`` of the mixed program of bucket ``S``:
        "tokens" or "rows", and the rows its page write scatters a layer."""
        return llama.kv_write_form(
            self.model_cfg, self.cfg.max_batch_size * S, self.step_tokens)

    def _count_context(self, ctx: np.ndarray, passes_of: np.ndarray,
                       passes: int = 1) -> None:
        """Count what a dispatch's attention reader is handed, a layer's
        worth: ``ctx`` [rows] each row's context after the dispatch,
        ``passes_of`` [rows] the model passes in which the row attends (0 or
        False: an idle row; a row of a fused block writes a token a pass, so
        its context was one shorter the pass before), ``passes`` the passes
        the program runs."""
        P = self.cfg.page_size
        back = np.arange(passes)[None, :]
        live = np.where(
            back < np.asarray(passes_of, np.int64)[:, None],
            np.asarray(ctx, np.int64)[:, None] - back, 0)
        obs.ATTN_CONTEXT_TOKENS.inc(int(live.sum()), what="live")
        if self.kernels.attn == "xla":  # every row's whole table, each pass
            read = (self.cfg.max_batch_size * self.cfg.max_pages_per_seq
                    * P * passes)
        else:                           # the live rows' pages
            read = int((-(-live // P) * P).sum())
        obs.ATTN_CONTEXT_TOKENS.inc(read, what="read")

    def _count_scan(self, real: int, computed: int) -> None:
        """Count a dispatch's selective-scan steps, over all the Mamba
        layers of a model that has them: ``real`` tokens among the
        ``computed`` slots (rows x slots a pass) the program's scan walks
        under XLA; the scan kernel walks a row's own tokens and no more."""
        if self._scan_layers:
            if self.kernels.state == "pallas-ssm":
                computed = real
            obs.SSM_SCAN_STEPS.inc(real * self._scan_layers, kind="real")
            obs.SSM_SCAN_STEPS.inc(
                computed * self._scan_layers, kind="computed")

    def _count_step_tokens(self, S: int, real: int) -> str:
        """Count a mixed dispatch of ``real`` tokens; returns the width its
        dense segments run over, as the counter's label has it (the step
        clock's ticket carries it to the pull)."""
        self._count_scan(real, self.cfg.max_batch_size * S)
        width = self._step_rows(S, real)
        obs.STEP_TOKENS.inc(real, kind="real")
        obs.STEP_TOKENS.inc(width, kind="computed")
        obs.MIXED_DISPATCH_WIDTH.inc(width=str(width))
        obs.KV_WRITE_ROWS.inc(real, kind="real")
        obs.KV_WRITE_ROWS.inc(self._kv_write(S)[1], kind="scattered")
        return str(width)

    def mixed_hosted(self, seq_id: int) -> bool:
        """True when this sequence needs host-side per-token work — a
        constrained-decoding mask, logprobs, or a logit bias/penalty —
        that the fused mixed program does not serve. The scheduler routes
        ticks involving such rows to the split prefill/decode path."""
        with self.lock:
            s = self.sequences.get(seq_id)
            if s is None:
                return False
            return bool(
                s.mask_fn is not None
                or s.params.logprobs
                or self._needs_bias(s)
            )

    def prefill_progress(self, seq_id: int) -> tuple[int, int]:
        """(tokens already prefilled, prompt length) for an admitting
        sequence — the scheduler's input for sizing mixed-step chunks."""
        with self.lock:
            return self._prefilling[seq_id], self.sequences[seq_id].prompt_len

    def step_mixed(
        self, decode_ids: list[int], prefill_chunks: dict[int, int]
    ) -> tuple[dict[int, list[int]], dict[int, Any]]:
        """ONE device dispatch that advances every given decode lane by
        one token AND runs one prefill chunk for each admitting sequence
        in ``prefill_chunks`` ({seq_id: chunk tokens}) — the unified mixed
        step. The chunk rows pad into the smallest ``mixed_buckets`` entry
        holding the largest chunk, decode rows ride at q_len=1, and the
        whole batch shares one weight stream (the split tick streams
        weights once for the prefill program and again for the decode
        program; on a weight-streaming-bound model that is the dominant
        per-tick cost). TTFT stops being quantized to decode-block
        boundaries because an admitting prompt advances every tick.

        The mixed path bypasses the device-resident block-decode carry, so
        any in-flight pipelined state is flushed first (same contract as
        ``step``); rows needing host-side per-token work are the caller's
        job to exclude (see ``mixed_hosted``).

        Returns ``(decode_out, prefill_out)``: decode_out maps each
        advanced decode sequence to its new token; prefill_out follows the
        ``prefill_batch`` contract ({seq_id: fully_prefilled | Exception},
        row-local failures isolated). Unlike ``step``, a raising stream
        callback on a decode row does NOT propagate — the row finishes
        with reason "error" (the scheduler's reap path surfaces it) so the
        prefill results of the same dispatch are never lost. A failed
        DISPATCH cleans up every chunk admission, rolls back the decode
        rows' one-token page bookings, and re-raises."""
        with self.lock:
            self._async_settle()
            while self._inflight or self._lane_of:
                # Settle the pipelined block-decode state: its device
                # carry tracks lane write offsets that a mixed dispatch
                # would silently desync.
                try:
                    self._flush_and_invalidate()
                except Exception:  # noqa: BLE001 - raising stream callback
                    # A pulled block's raising stream callback belongs to
                    # its OWN row (already finished as "error"; the reap
                    # path surfaces it). Propagating from here would fail
                    # this tick's innocent chunk admissions with another
                    # client's disconnect — keep draining instead.
                    log.exception(
                        "stream callback raised while settling pipelined "
                        "state for a mixed dispatch; row isolated"
                    )
            decode = [
                self.sequences[s] for s in decode_ids
                if s in self.sequences and not self.sequences[s].done
            ]
            B = self.cfg.max_batch_size
            if len(decode) + len(prefill_chunks) > B:
                raise ValueError(
                    f"mixed batch of {len(decode)} decode + "
                    f"{len(prefill_chunks)} prefill rows exceeds "
                    f"max_batch_size={B}"
                )
            chunk_info: list[tuple[int, Sequence, int, int]] = []
            smax = 1
            for sid, want in prefill_chunks.items():
                seq = self.sequences[sid]
                done = self._prefilling[sid]
                c = self.alloc.clamp_chunk(sid, done, seq.prompt_len, min(
                    want, self.cfg.mixed_buckets[-1], seq.prompt_len - done
                ))
                chunk_info.append((sid, seq, done, c))
                smax = max(smax, c)
            carried = len(decode) + sum(c for *_, c in chunk_info)
            if carried > self.step_tokens:
                # No program is warmed for it: the scheduler plans inside
                # max_step_tokens, which the packed width covers.
                raise ValueError(
                    f"mixed batch of {carried} tokens exceeds the step's "
                    f"{self.step_tokens} (max_step_tokens="
                    f"{self.cfg.max_step_tokens})"
                )
            # Book the token each decode row is about to write (the
            # step() contract: a row that cannot grow finishes as
            # truncated instead of killing the dispatch).
            grown: list[Sequence] = []
            for s in decode:
                try:
                    self.alloc.extend(s.seq_id, 1)
                    grown.append(s)
                except OutOfPages:
                    s.done = True
                    s.finish_reason = "length"
                    obs.PREEMPTIONS.inc()
                    obs.flight.record("preemption", seq_id=s.seq_id)
                    log.warning(
                        "seq %d truncated: KV page budget exhausted",
                        s.seq_id,
                    )
            decode = grown
            decode_out: dict[int, list[int]] = {}
            prefill_out: dict[int, Any] = {}
            if not decode and not prefill_chunks:
                return decode_out, prefill_out
            S = self._mixed_bucket(smax)
            with self._building_arrays():
                tokens = np.full((B, S), self.tokenizer.pad_id, np.int32)
                starts = np.zeros((B,), np.int32)
                qlens = np.zeros((B,), np.int32)
                tables = np.full((B, self.alloc.table_width), -1, np.int32)
                for i, s in enumerate(decode):
                    tokens[i, 0] = (
                        s.tokens[-1] if s.tokens else self.tokenizer.bos_id
                    )
                    # extend(1) above made alloc.length = written + 1; the
                    # row writes (and attends from) the written offset.
                    starts[i] = self.alloc.length(s.seq_id) - 1
                    qlens[i] = 1
                    tables[i] = self._pass_row(s.seq_id, int(starts[i]), 1)
                base = len(decode)
                for j, (sid, seq, done, c) in enumerate(chunk_info):
                    tokens[base + j, :c] = seq.prompt_ids[done:done + c]
                    starts[base + j] = done
                    qlens[base + j] = c
                    tables[base + j] = self._pass_row(sid, done, c)
                slots: list[Sequence | None] = (
                    decode + [seq for _, seq, _, _ in chunk_info]
                )
                slots += [None] * (B - len(slots))
                temps, top_k, top_p, _ = self._sampling_arrays(slots, B)
            perf = get_perf_stats()
            with obs.phase("plan", part="account"):
                width = self._count_step_tokens(S, int(qlens.sum()))
                self._count_context(starts + qlens, qlens > 0)
                ticket = self.step_clock.enqueue(width)
                tick_id, t_disp, _ = ticket
                # Dispatch-to-dispatch interval (the async A/B's comparison
                # basis): time since the previous mixed dispatch's enqueue
                # returned — in this SYNC tick it spans the blocking token
                # pull (the device's whole step) plus all host
                # post-processing, the span the async runtime overlaps.
                if self._mixed_gap_stamp is not None:
                    obs.STEP_HOST_GAP_SECONDS.observe(
                        t_disp - self._mixed_gap_stamp, mode="sync"
                    )
            try:
                with obs.phase("dispatch", tick=tick_id), self.mesh_ctx():
                    with obs.phase("dispatch", part="place"):
                        self._sample_key, sub = jax.random.split(
                            self._sample_key)
                        (tokens_d, starts_d, qlens_d, tables_d, temps_d,
                         top_k_d, top_p_d) = (
                            jnp.asarray(a) for a in (
                                tokens, starts, qlens, tables, temps, top_k,
                                top_p))
                    with obs.phase("dispatch", part="call"), \
                            annotate("engine.mixed_step"):
                        toks_d, self.cache = self._mixed_sample_jit(
                            self.params, tokens_d, starts_d, qlens_d,
                            self.cache, tables_d, sub, temps_d, top_k_d,
                            top_p_d,
                        )
                self._mixed_gap_stamp = time.perf_counter()
                sampled = self._pull("mixed", int(S), ticket, toks_d)
            except Exception:
                # The decode rows' +1 bookings are for tokens this failed
                # dispatch never wrote; leaving them would put an
                # unwritten hole inside the attended window next step.
                for s in decode:
                    if not s.done:
                        self.alloc.truncate(
                            s.seq_id, self.alloc.length(s.seq_id) - 1
                        )
                for sid, *_ in chunk_info:
                    self._drop_admission(sid)
                raise
            measured_s = time.perf_counter() - t_disp
            with obs.phase("plan", part="account"):
                n_prefill = int(sum(c for *_, c in chunk_info))
                if n_prefill:
                    perf.record_metric(
                        "engine.prefill_tokens", n_prefill, "tok"
                    )
                    obs.PREFILL_TOKENS.inc(n_prefill)
                from .decode_loop import record_mixed_dispatch

                # Attribution composition: decode lanes attend their whole
                # written context; chunk rows read their prefix + chunk.
                # The sync tick's dispatch+pull wall time is a real
                # synchronous measurement, so it also feeds the drift gauge.
                dec_ctx = int(
                    sum(int(starts[i]) + 1 for i in range(len(decode))))
                record_mixed_dispatch(
                    decode_rows=len(decode),
                    prefill_tokens=n_prefill,
                    attr=self.attr,
                    attr_kw=dict(
                        q_tokens=len(decode) + n_prefill,
                        kv_read_tokens=dec_ctx + int(
                            sum(d + c for _sid, _seq, d, c in chunk_info)
                        ),
                        kv_write_tokens=len(decode) + n_prefill,
                        attn_q_ctx=dec_ctx + int(sum(
                            obs.attribution.prefill_attn_positions(d, c)
                            for _sid, _seq, d, c in chunk_info
                        )),
                        measured_s=measured_s,
                    ),
                )
                obs.flight.record(
                    "dispatch", op="mixed",
                    decode_seq_ids=[s.seq_id for s in decode],
                    prefill_seq_ids=[sid for sid, *_ in chunk_info],
                    bucket=int(S), prefill_tokens=n_prefill,
                    budget=self.cfg.max_step_tokens, tick=tick_id,
                )
            with self._accepting(tick=tick_id):
                for i, s in enumerate(decode):
                    tok = int(sampled[i])
                    dspan = s.decode_span
                    try:
                        self._accept_token(s, tok)
                    except Exception:  # noqa: BLE001 - raising stream callback
                        # Row-local isolation WITHOUT propagation: the reap
                        # path surfaces finish_reason "error"; raising here
                        # would lose the same dispatch's prefill results.
                        s.done = True
                        s.finish_reason = s.finish_reason or "error"
                        self.alloc.truncate(s.seq_id, self._host_written(s))
                    decode_out[s.seq_id] = [tok]
                    if dspan is not None:
                        t0 = time.perf_counter()
                        dspan.child(
                            "mixed_step", t_disp, t0, tokens=1, tick=tick_id,
                        )
                        self._summed("account", t0)
                for j, (sid, seq, done, c) in enumerate(chunk_info):
                    if done + c < seq.prompt_len:
                        self._prefilling[sid] = done + c
                        prefill_out[sid] = False
                        continue
                    del self._prefilling[sid]
                    token = int(sampled[base + j])
                    seq.ttft_s = time.perf_counter() - seq.started_s
                    perf.record_metric("engine.ttft", seq.ttft_s * 1e3, "ms")
                    self._first_token_obs(seq)
                    try:
                        self._accept_token(seq, token)
                    except Exception as e:  # noqa: BLE001 - stream callback
                        self._drop_admission(sid)
                        prefill_out[sid] = e
                        continue
                    prefill_out[sid] = True
            with obs.phase("commit", part="account"):
                if decode:
                    perf.record_metric(
                        "engine.decode_tokens", len(decode), "tok"
                    )
                self._observe_occupancy()
            return decode_out, prefill_out

    # -- grammar fast-forward (forced-token runs) ----------------------------
    def _note_ffwd_ineligible(self, s: Sequence) -> None:
        """Count a constrained row that cannot fast-forward (hosted mask /
        tables over budget / logprobs / logit bias) — once per sequence,
        under the fallback reason that separates "can't fast-forward"
        from "can't async"."""
        if s.seq_id in self._ffwd_noted:
            return
        self._ffwd_noted.add(s.seq_id)
        obs.ASYNC_FALLBACKS.inc(reason="ffwd_ineligible")

    def _ffwd_candidate(self, s: Sequence) -> tuple[Any, list[int]] | None:
        """(fsm, forced run D) when this row can splice a forced run right
        now, else None. The dispatch inputs are ``[last_token] + D`` and
        the masked sample after D is itself deterministic — eos when the
        run ended there (trimmed off D: a masked sample at an eos-only
        state yields eos at ANY temperature), or D's forced successor when
        the cap cut the run short. D is clamped so the append (D plus the
        sampled token) never overshoots max_tokens or the largest mixed
        bucket."""
        if s.mask_fn is None or s.done or not s.tokens:
            return None
        if s.params.logprobs or self._needs_bias(s):
            self._note_ffwd_ineligible(s)
            return None
        from .constrained import device_table_fsm

        fsm = device_table_fsm(s.mask_fn)
        if fsm is None:
            self._note_ffwd_ineligible(s)
            return None
        run = s.mask_fn.forced_run(s.tokens)
        if run and run[-1] == fsm.eos_id:
            run = run[:-1]
        room = s.params.max_tokens - len(s.tokens) - 1
        run = run[: max(0, min(room, self.cfg.mixed_buckets[-1] - 1))]
        if not run:
            return None
        return fsm, run

    def ffwd_step(self, seq_ids: list[int]) -> dict[int, list[int]]:
        """Grammar fast-forward: for constrained rows whose current FSM
        state forces a run of singleton-mask tokens, splice the whole run
        into the paged KV as ONE multi-token append (the q_len>1 path the
        mixed program already serves for prefill chunks) and sample only
        the token AFTER the run — every forced token skips a full forward
        pass. Greedy output is byte-identical with the feature off: a
        masked sample over a singleton support produces that token at any
        temperature.

        The pre-scan never disturbs the block pipeline: rows with
        device-resident in-flight tokens have stale host token lists and
        are skipped (no flush-thrash when nothing is forced). Only when a
        settled row has a forced run does this settle the pipelines and
        dispatch. Returns {seq_id: accepted tokens} (empty when nothing
        was eligible)."""
        with self.lock:
            if not (self.cfg.grammar_ffwd and self.cfg.mixed_batching):
                return {}

            def scan(skip_inflight: bool) -> list[tuple]:
                out = []
                for sid in seq_ids:
                    s = self.sequences.get(sid)
                    if s is None or s.done:
                        continue
                    # A lane ASSIGNMENT alone does not stale the host
                    # token list — only booked steps still in flight do
                    # (the carry's pending write slot is a page booking,
                    # not a token). Scanning settled lane rows is what
                    # lets ffwd engage between blocks without flushing
                    # speculatively.
                    if skip_inflight and (
                        sid in self._inflight_steps
                        or self._async._inflight_toks.get(sid, 0)
                    ):
                        continue
                    cand = self._ffwd_candidate(s)
                    if cand is not None:
                        out.append((s, *cand))
                return out

            if not scan(True):
                return {}
            self._async_settle()
            while self._inflight or self._lane_of:
                try:
                    self._flush_and_invalidate()
                except Exception:  # noqa: BLE001 - raising stream callback
                    log.exception(
                        "stream callback raised while settling pipelined "
                        "state for a ffwd dispatch; row isolated"
                    )
            # Re-scan on settled host state: pulled blocks appended tokens
            # (and may have finished rows) during the flush.
            cands = scan(False)
            if not cands:
                return {}
            # One shared table set per dispatch: keep the first fsm's
            # group, the rest retry next tick.
            fsm0 = cands[0][1]
            cands = [
                c for c in cands if c[1] is fsm0
            ][: self.cfg.max_batch_size]
            rows: list[tuple[Sequence, list[int]]] = []
            room = self.step_tokens
            for s, _fsm, run in cands:
                # A run cut to what the step still carries (its row's last
                # token rides too) costs the row a later step and changes
                # no token: what follows a forced token is forced as well.
                run = run[: max(0, room - 1)]
                if not run:
                    break
                try:
                    self.alloc.extend(s.seq_id, 1 + len(run))
                except OutOfPages:
                    # Roll back partially-grabbed pages and leave the row
                    # to the normal decode path, which finishes it as
                    # "length" when the pool is truly dry.
                    self.alloc.truncate(
                        s.seq_id, self.alloc.length(s.seq_id)
                    )
                    continue
                rows.append((s, run))
                room -= 1 + len(run)
            if not rows:
                return {}
            B = self.cfg.max_batch_size
            S = self._mixed_bucket(max(1 + len(r) for _, r in rows))
            tokens = np.full((B, S), self.tokenizer.pad_id, np.int32)
            starts = np.zeros((B,), np.int32)
            qlens = np.zeros((B,), np.int32)
            emits = np.zeros((B,), bool)
            ov_fsm = np.zeros((B,), np.int32)  # 0 = FREE sentinel row
            tables = np.full((B, self.alloc.table_width), -1, np.int32)
            temps = np.zeros((B,), np.float32)
            top_k = np.zeros((B,), np.int32)
            top_p = np.ones((B,), np.float32)
            for i, (s, run) in enumerate(rows):
                q = 1 + len(run)
                tokens[i, :q] = [s.tokens[-1]] + run
                # extend above made alloc.length = written + q; the row
                # writes its q inputs from the written offset.
                starts[i] = self.alloc.length(s.seq_id) - q
                qlens[i] = q
                emits[i] = True
                tables[i] = self._pass_row(s.seq_id, int(starts[i]), q)
                # The masked sample applies the state AFTER the appended
                # run (+1: device-table row 0 is the FREE sentinel).
                ov_fsm[i] = s.mask_fn.dfa_state(s.tokens + run) + 1
                temps[i] = s.params.temperature
                top_k[i] = s.params.top_k
                top_p[i] = s.params.top_p
            perf = get_perf_stats()
            width = self._count_step_tokens(S, int(qlens.sum()))
            self._count_context(starts + qlens, qlens > 0)
            ticket = self.step_clock.enqueue(width)
            tick_id, t_disp, _ = ticket
            try:
                with obs.phase("dispatch", tick=tick_id), self.mesh_ctx():
                    with obs.phase("dispatch", part="place"):
                        fm, fd = self._fsm_device_tables(fsm0)
                        self._sample_key, sub = jax.random.split(
                            self._sample_key)
                        # under the mesh context, as warm-up made them:
                        # the jit cache keys even these on it
                        zb = jnp.zeros((B,), bool)
                        zi = jnp.zeros((B,), jnp.int32)
                        (tokens_d, starts_d, qlens_d, emits_d, tables_d,
                         temps_d, top_k_d, top_p_d, ov_fsm_d) = (
                            jnp.asarray(a) for a in (
                                tokens, starts, qlens, emits, tables, temps,
                                top_k, top_p, ov_fsm))
                    with obs.phase("dispatch", part="call"), \
                            annotate("engine.ffwd_step"):
                        toks_d, self.cache, _fsm_d = self._mixed_carry_jit(
                            self.params,
                            tokens_d,
                            zb,  # use_carry: all rows override from host
                            zi,  # carry tokens (unused at use_carry=False)
                            starts_d,
                            qlens_d,
                            emits_d,
                            self.cache,
                            tables_d,
                            sub,
                            temps_d,
                            top_k_d,
                            top_p_d,
                            fsm_mask=fm, fsm_dest=fd,
                            carry_fsm=zi, ov_fsm=ov_fsm_d,
                        )
                self._mixed_gap_stamp = time.perf_counter()
                sampled = self._pull("ffwd", int(S), ticket, toks_d)
            except Exception:
                # Nothing was accepted: roll every booking back to
                # written truth before surfacing the dispatch error.
                for s, _run in rows:
                    if not s.done:
                        self.alloc.truncate(s.seq_id, self._host_written(s))
                raise
            measured_s = time.perf_counter() - t_disp
            perf.record_metric(
                "engine.ffwd_dispatch", measured_s * 1e3, "ms"
            )
            obs.DECODE_DISPATCHES.inc(kind="ffwd")
            n_forced = int(sum(len(r) for _, r in rows))
            q_total = n_forced + len(rows)
            self.attr.dispatch(
                "ffwd_append",
                q_tokens=q_total,
                kv_read_tokens=int(sum(
                    int(starts[i]) + int(qlens[i])
                    for i in range(len(rows))
                )),
                kv_write_tokens=q_total,
                attn_q_ctx=int(sum(
                    obs.attribution.prefill_attn_positions(
                        int(starts[i]), int(qlens[i])
                    )
                    for i in range(len(rows))
                )),
                measured_s=measured_s,
            )
            obs.flight.record(
                "dispatch", op="ffwd",
                decode_seq_ids=[s.seq_id for s, _ in rows],
                bucket=int(S), forced_tokens=n_forced, tick=tick_id,
            )
            from .decode_loop import record_ffwd_append

            decode_out: dict[int, list[int]] = {}
            produced = 0
            with self._accepting(tick=tick_id):
                for i, (s, run) in enumerate(rows):
                    accepted: list[int] = []
                    dspan = s.decode_span
                    try:
                        for t in run:
                            if s.done:
                                break
                            self._accept_token(s, t)
                            accepted.append(t)
                        if not s.done:
                            tok = int(sampled[i])
                            self._accept_token(s, tok)
                            accepted.append(tok)
                    except Exception:  # noqa: BLE001 - raising stream callback
                        # Row-local isolation, same contract as step_mixed:
                        # the reap path surfaces finish_reason "error".
                        s.done = True
                        s.finish_reason = s.finish_reason or "error"
                    if s.done:
                        # Stop string / EOS / max_tokens (or a raising
                        # callback) landed mid-append: the tail of the booked
                        # run is dead content — roll back to written truth.
                        self.alloc.truncate(s.seq_id, self._host_written(s))
                    n_ff = min(len(accepted), len(run))
                    if n_ff:
                        record_ffwd_append(
                            s.seq_id, n_ff, attr=self.attr,
                            request_id=obs.flight.request_id_of(s.trace),
                        )
                    if dspan is not None:
                        dspan.child(
                            "ffwd_step", t_disp, time.perf_counter(),
                            tokens=len(accepted), tick=tick_id,
                        )
                    decode_out[s.seq_id] = accepted
                    produced += len(accepted)
            with obs.phase("commit", part="account"):
                if produced:
                    perf.record_metric("engine.decode_tokens", produced, "tok")
                self._observe_occupancy()
            return decode_out

    def _sampling_arrays(
        self, seqs: list[Sequence | None], B: int
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray | None]:
        """Per-slot (temps, top_k, top_p, allowed-mask-or-None) arrays."""
        temps = np.zeros((B,), np.float32)
        top_k = np.zeros((B,), np.int32)
        top_p = np.ones((B,), np.float32)
        mask = None
        for i, s in enumerate(seqs):
            if s is None:
                continue
            temps[i] = s.params.temperature
            top_k[i] = s.params.top_k
            top_p[i] = s.params.top_p
            if s.mask_fn is not None:
                if mask is None:
                    mask = np.ones((B, self.model_cfg.vocab_size), bool)
                m = s.mask_fn(s.tokens)
                # Checkpoints pad the embedding vocab past the tokenizer's
                # (e.g. Qwen 152064 vs ~151.7k): padded ids are forbidden on
                # constrained rows — the tokenizer could never decode them.
                n = min(len(m), mask.shape[1])
                mask[i, :n] = m[:n]
                mask[i, n:] = False
        return temps, top_k, top_p, mask

    def _fsm_device_tables(self, fsm) -> tuple[jax.Array, jax.Array]:
        """Device-resident ([S+1, V] mask, dest) for one TokenFSM, cached
        (keyed by identity, holding the fsm so a reused id() can't alias).
        Tiny LRU: schemas churn rarely and each table set re-specializes
        the decode-block program anyway."""
        ent = self._fsm_dev.get(id(fsm))
        if ent is not None and ent[0] is fsm:
            return ent[1], ent[2]
        mask, dest = fsm.dense_tables()
        m, d = jnp.asarray(mask), jnp.asarray(dest)
        self._fsm_dev[id(fsm)] = (fsm, m, d)
        while len(self._fsm_dev) > 2:
            self._fsm_dev.pop(next(iter(self._fsm_dev)))
        return m, d

    @staticmethod
    def _needs_bias(s: Sequence) -> bool:
        p = s.params
        return bool(
            p.logit_bias or p.presence_penalty or p.frequency_penalty
        )

    def _bias_array(
        self, seqs: list[Sequence | None], B: int
    ) -> np.ndarray | None:
        """Additive [B, V] logit bias, or None when no row needs one:
        OpenAI logit_bias entries plus presence/frequency penalties over
        each row's generated-so-far token counts (including tokens
        generated before an engine restart — params.penalty_history).
        The static logit_bias row is cached per sequence and the batch
        buffer reused, so pure-logit_bias rows cost a memcpy per step."""
        V = self.model_cfg.vocab_size
        bias = None
        for i, s in enumerate(seqs):
            if s is None or not self._needs_bias(s):
                continue
            if bias is None:
                if self._bias_buf is None or self._bias_buf.shape != (B, V):
                    self._bias_buf = np.zeros((B, V), np.float32)
                else:
                    self._bias_buf[:] = 0.0
                bias = self._bias_buf
            p = s.params
            if s.static_bias is None:
                row = np.zeros((V,), np.float32)
                for tid, b in p.logit_bias:
                    if 0 <= tid < V:
                        row[tid] += b
                s.static_bias = row
            bias[i] = s.static_bias
            if p.presence_penalty or p.frequency_penalty:
                counts = s.penalty_counts
                if counts is None and p.penalty_history:
                    # Before the first accepted token: seed from salvage.
                    counts = {}
                    for t in p.penalty_history:
                        counts[t] = counts.get(t, 0) + 1
                for tid, c in (counts or {}).items():
                    if 0 <= tid < V:
                        bias[i, tid] -= (
                            p.presence_penalty + p.frequency_penalty * c
                        )
        return bias

    def _pull(
        self, program: str, bucket: int, ticket: tuple[int, float, str],
        out_d: jax.Array, alone: bool = True,
    ) -> np.ndarray:
        """Bring a dispatched step's output to the host: the ``wait`` phase
        (the scheduler thread blocks on the device here) and the step
        clock's reading of when the step finished. ``alone`` says that
        nothing is enqueued behind the step pulled (the caller knows its
        pipeline): the device then idles from the moment the step ends
        until the next dispatch, and the wait is the part ``alone``, else
        ``pipelined``."""
        with obs.phase(
            "wait", part="alone" if alone else "pipelined", tick=ticket[0]
        ):
            waited = not out_d.is_ready()
            out = np.asarray(out_d)
            self.step_clock.pulled(program, bucket, ticket, waited)
        return out

    def _sample_one(
        self, logits: jax.Array, seqs: list[Sequence],
        step: tuple[str, int, tuple[int, float]] | None = None,
    ) -> np.ndarray:
        """Sample one token per row from a prefill program's ``logits``.
        ``step`` is (program, bucket, ticket) of the dispatch that made
        them: the token pull below is the first time the host waits for
        it (so its step-clock sample includes the small sample program).
        Warm-up passes none and is not clocked."""
        B = logits.shape[0]
        temps, top_k, top_p, mask = self._sampling_arrays(seqs, B)
        bias = self._bias_array(seqs, B)
        if bias is not None:
            logits = logits + jnp.asarray(bias)
        # Under the mesh context ALWAYS: the jit cache keys on the ambient
        # mesh, so a call outside `with self.mesh:` recompiles the sampler
        # (and the eager random.split helpers) with an identical signature
        # (r04: warmed sample programs were recompiled inside the serving
        # window because prefill_batch sampled outside the mesh block
        # warmup used).
        with self.mesh_ctx():
            self._sample_key, sub = jax.random.split(self._sample_key)
            tok = self._sample_jit(
                logits,
                sub,
                jnp.asarray(temps),
                jnp.asarray(top_k),
                jnp.asarray(top_p),
                None if mask is None else jnp.asarray(mask),
            )
        toks = np.asarray(tok) if step is None else self._pull(*step, tok)
        if any(s is not None and s.params.logprobs for s in seqs):
            # First-token logprobs (prefill's sampled token), on the host
            # in numpy: admission is not the steady-state hot loop, and an
            # eager device op here is a program no warmup family covers.
            lg = np.asarray(logits).astype(np.float32)
            lg -= lg.max(axis=-1, keepdims=True)
            lg -= np.log(np.exp(lg).sum(axis=-1, keepdims=True))
            tv = min(self.tokenizer.vocab_size, lg.shape[1])
            for i, s in enumerate(seqs):
                if s is None or not s.params.logprobs:
                    continue
                row, n = lg[i].copy(), s.params.top_logprobs
                row[tv:] = -np.inf  # padded-vocab ids: unrenderable
                top = []
                if n > 0:
                    idx = np.argpartition(-row, n)[:n]
                    idx = idx[np.argsort(-row[idx])]
                    top = [(int(j), float(row[j])) for j in idx]
                s.logprob_data.append(
                    {"logprob": float(row[int(toks[i])]), "top": top}
                )
        return toks

    # -- observability -------------------------------------------------------
    def _observe_occupancy(self) -> None:
        """Refresh the engine-step gauges (KV page utilization, batch
        occupancy) and delta-sync the allocator's cumulative prefix-trie
        eviction count into the obs counter. Cheap host math — called from
        admission, step, and finish paths under the engine lock."""
        free = self.alloc.free_pages
        obs.KV_PAGES_FREE.set(free)
        obs.KV_PAGE_UTILIZATION.set(1.0 - free / max(1, self.alloc.num_pages))
        running = sum(1 for s in self.sequences.values() if not s.done)
        obs.BATCH_OCCUPANCY.set(running / max(1, self.cfg.max_batch_size))
        obs.RUNNING_SEQUENCES.set(len(self.sequences))
        if self.alloc.state_slots:
            live, snaps = self.alloc.state_slots_in_use()
            obs.STATE_SLOTS_IN_USE.set(live, kind="live")
            obs.STATE_SLOTS_IN_USE.set(snaps, kind="snapshot")
            for i, (event, total) in enumerate((
                ("taken", self.alloc.snapshots_taken),
                ("evicted", self.alloc.snapshots_evicted),
            )):
                if total > self._snapshots_seen[i]:
                    obs.STATE_SNAPSHOTS.inc(
                        total - self._snapshots_seen[i], event=event)
                    self._snapshots_seen[i] = total
        ev = self.alloc.evictions
        if ev > self._evictions_seen:
            obs.PREFIX_EVICTIONS.inc(ev - self._evictions_seen)
            obs.flight.record(
                "prefix_eviction", count=ev - self._evictions_seen
            )
            self._evictions_seen = ev

    def _first_token_obs(self, seq: Sequence) -> None:
        """Prefill finished and the first token was sampled: observe TTFT,
        record the prefill span, and open the request's decode span (per-
        dispatch block spans attach under it; closed when the sequence
        finishes). A TTFT past the SLO threshold is a flight-recorder
        anomaly: the ring dump holds the admissions and dispatch
        compositions of the seconds leading up to the slow first token.
        All of it observes: summed into ``commit``'s ``account`` part."""
        t0 = time.perf_counter()
        obs.TTFT_SECONDS.observe(seq.ttft_s)
        cls = obs.trace.class_of(seq.trace)
        if cls:
            obs.CLASS_TTFT_SECONDS.observe(seq.ttft_s, **{"class": cls})
        obs.attribution.record_goodput(seq.ttft_s, "prefill", slo_class=cls)
        ttft_ms = round(seq.ttft_s * 1e3, 3)
        rid = obs.flight.request_id_of(seq.trace)
        obs.flight.record(
            "ttft", seq_id=seq.seq_id, ttft_ms=ttft_ms, request_id=rid
        )
        thr = obs.flight.ttft_threshold_s()
        if thr > 0 and seq.ttft_s > thr:
            obs.flight.anomaly(
                "ttft_breach", seq_id=seq.seq_id, ttft_ms=ttft_ms,
                threshold_ms=round(thr * 1e3, 3), request_id=rid,
            )
        now = time.perf_counter()
        if seq.trace is not None:
            seq.trace.child(
                "prefill", seq.started_s, now,
                prompt_tokens=seq.prompt_len,
            )
            seq.decode_span = seq.trace.start_child("decode")
        self._summed("account", t0)

    def _accept_token(self, seq: Sequence, token: int) -> None:
        """Fold one token into its sequence, in the ``commit`` phase. What
        recurs a token and is not the accept itself is timed here and
        summed into parts of its own (``obs.add_part``): the counters and
        the span's close into ``account``, the stream callback into
        ``stream``, the stop strings' decode into ``stop_scan``."""
        seq.tokens.append(token)
        now = time.perf_counter()
        obs.DECODE_TOKENS.inc()
        if seq.last_tok_s:
            obs.ITL_SECONDS.observe(now - seq.last_tok_s)
            cls = obs.trace.class_of(seq.trace)
            if cls:
                obs.CLASS_ITL_SECONDS.observe(
                    now - seq.last_tok_s, **{"class": cls}
                )
        else:
            seq.first_tok_tick = self.sched_tick
        seq.last_tok_s = now
        seq.last_tok_tick = self.sched_tick
        self._summed("account", now)
        p = seq.params
        if p.presence_penalty or p.frequency_penalty:
            if seq.penalty_counts is None:
                seq.penalty_counts = {}
                for t in p.penalty_history:
                    seq.penalty_counts[t] = seq.penalty_counts.get(t, 0) + 1
            seq.penalty_counts[token] = seq.penalty_counts.get(token, 0) + 1
        if seq.stream is not None:
            t0 = time.perf_counter()
            try:
                seq.stream(token)
            finally:
                self._summed("stream", t0)
        if token == self.tokenizer.eos_id:
            seq.done = True
            seq.finish_reason = "stop"
        elif len(seq.tokens) >= seq.params.max_tokens:
            seq.done = True
            seq.finish_reason = "length"
        elif seq.params.stop:
            t0 = time.perf_counter()
            hit = self._hit_stop_string(seq)
            self._summed("stop_scan", t0)
            if hit:
                seq.done = True
                seq.finish_reason = "stop"
        if seq.done and seq.decode_span is not None:
            t0 = time.perf_counter()
            obs.attribution.record_goodput(
                seq.decode_span.duration_s(), "decode_active",
                slo_class=obs.trace.class_of(seq.trace),
            )
            seq.decode_span.close(
                tokens=len(seq.tokens), finish_reason=seq.finish_reason
            )
            seq.decode_span = None
            self._summed("account", t0)

    def _hit_stop_string(self, seq: Sequence) -> bool:
        """Check the decoded tail for any stop string, so generation halts at
        the stop instead of burning decode steps to max_tokens. The window is
        sized in TOKENS: one char can span up to 4 byte-level tokens (UTF-8),
        so a char-sized window would miss long multi-byte stop strings."""
        longest = max(len(s) for s in seq.params.stop)
        tail_tokens = seq.tokens[-(longest * 4 + 8) :]
        tail = self.tokenizer.decode(tail_tokens)
        return any(s in tail for s in seq.params.stop)

    # -- pipelined decode internals ------------------------------------------
    def _host_written(self, seq: Sequence) -> int:
        """Tokens actually written to this sequence's pages: the prompt plus
        every accepted token except the last sampled one (never written)."""
        return seq.prompt_len + max(0, len(seq.tokens) - 1)

    def _free_lane(self, seq_id: int) -> None:
        lane = self._lane_of.pop(seq_id, None)
        if lane is not None:
            self._lanes[lane] = None

    def _flush_and_invalidate(self) -> None:
        """Drain in-flight dispatches and drop the device-resident decode
        state, rolling page bookings back to written content. Called before
        the legacy single-step path touches a lane-held sequence (its
        extend/truncate bookkeeping would desync the device carry)."""
        while self._inflight:
            self._pull_oldest()
        for sid in list(self._lane_of):
            s = self.sequences.get(sid)
            if s is not None and not s.done:
                self.alloc.truncate(sid, self._host_written(s))
        self._lanes = [None] * self.cfg.max_batch_size
        self._lane_of.clear()
        self._carry = None

    def _async_settle(self) -> None:
        """Commit every in-flight ASYNC mixed tick (results buffered for
        ``async_take_results``). Sync-lane entry points call this before
        touching sequence/allocator state the lookahead pipeline may
        still reference — the async dual of ``_flush_and_invalidate``."""
        if self._async.pending:
            self._async.flush()

    # -- async mixed pipeline (serving/async_runtime.py) ---------------------
    def step_mixed_async(
        self, decode_ids: list[int], prefill_chunks: dict[int, int]
    ) -> tuple[dict[int, list[int]], dict[int, Any]]:
        """The one-step-lookahead form of ``step_mixed``: dispatch this
        tick's batch and return the COMMITTED results so far — which, at
        ``cfg.async_depth`` > 1, lag the dispatch by up to depth-1 ticks.
        Decode-lane feedback stays device-resident between dispatches, so
        the host's post-processing of tick t overlaps tick t+1's device
        execution. Same exclusions as ``step_mixed`` (the caller routes
        hosted rows away — see ``mixed_async_hosted``); same return
        contract, with tokens/list values since several commits may land
        in one call."""
        with self.lock:
            while self._inflight or self._lane_of:
                try:
                    self._flush_and_invalidate()
                except Exception:  # noqa: BLE001 - raising stream callback
                    log.exception(
                        "stream callback raised while settling pipelined "
                        "state for an async mixed dispatch; row isolated"
                    )
            return self._async.step(decode_ids, prefill_chunks)

    def async_pending(self) -> int:
        """Dispatched-but-uncommitted async mixed ticks."""
        with self.lock:
            return self._async.pending

    def mixed_gap_break(self) -> None:
        """Mark a discontinuity in the mixed-tick cadence (scheduler went
        idle): the next mixed dispatch must not count the wait as host
        gap — opsagent_step_host_gap_seconds measures back-to-back ticks
        only."""
        self._mixed_gap_stamp = None

    def async_take_results(self) -> tuple[dict[int, list[int]], dict[int, Any]]:
        """Results committed by internal pipeline settles (parking,
        warmup, sync-lane fallbacks) since the last pickup — a finished
        admission must reach the scheduler even when its commit happened
        outside ``step_mixed_async``."""
        with self.lock:
            return self._async.take_results()

    def async_drain(self) -> tuple[dict[int, list[int]], dict[int, Any]]:
        """Flush the async pipeline and return everything committed."""
        with self.lock:
            self._async.flush()
            return self._async.take_results()

    def mixed_async_hosted(self, seq_id: int) -> bool:
        """True when this sequence cannot ride the ASYNC mixed lane: it
        needs host-side per-token work (logprobs, logit bias/penalties)
        or a constrained mask without dense device tables. Such rows
        route the tick to the existing sync lanes (``mixed_hosted`` /
        split path). Note the asymmetry with ``mixed_hosted``: a
        JsonConstraint WITH device tables is async-eligible — its mask
        comes from on-device FSM state."""
        with self.lock:
            s = self.sequences.get(seq_id)
            if s is None:
                return False
            if s.params.logprobs or self._needs_bias(s):
                return True
            if s.mask_fn is None:
                return False
            from .constrained import device_table_fsm

            return device_table_fsm(s.mask_fn) is None

    def note_ffwd_ineligible(self, seq_id: int) -> None:
        """Scheduler-facing form of ``_note_ffwd_ineligible``: count a
        row that a hosted-lane fallback just made ffwd-ineligible."""
        with self.lock:
            s = self.sequences.get(seq_id)
            if s is not None:
                self._note_ffwd_ineligible(s)

    def async_row_fsm(self, seq_id: int):
        """The dense-table TokenFSM behind this row's mask, or None. The
        scheduler uses it to keep each async dispatch on ONE shared table
        set (mixed-schema ticks fall back to the sync lanes)."""
        with self.lock:
            s = self.sequences.get(seq_id)
            if s is None:
                return None
            from .constrained import device_table_fsm

            return device_table_fsm(s.mask_fn)

    def _pull_oldest(self) -> dict[int, list[int]]:
        """Pull the oldest in-flight block's tokens (the one device->host
        round trip per dispatch) and fold them into host state. Records are
        pulled FIFO, so the host always sees a row's EOS before any of its
        later pad-only blocks."""
        toks_d, lane_seqs, budgets, ticket, program = (
            self._inflight.popleft()
        )
        # alone: no younger block is enqueued behind the one pulled
        toks = self._pull(
            *program, ticket, toks_d, alone=not self._inflight)
        with self._accepting(tick=ticket[0]):
            return self._commit_block(toks, lane_seqs, budgets, ticket)

    def _commit_block(
        self, toks, lane_seqs, budgets, ticket
    ) -> dict[int, list[int]]:
        """Fold one pulled decode block into host state (the commit phase:
        accept, stop scan, detokenize, stream, roll bookings back)."""
        perf = get_perf_stats()
        tick_id, t_disp, _ = ticket
        out: dict[int, list[int]] = {}
        produced = 0
        first_exc: BaseException | None = None
        for lane, sid in enumerate(lane_seqs):
            if sid is None or budgets[lane] == 0:
                continue
            left = self._inflight_steps.get(sid, 0) - int(budgets[lane])
            if left > 0:
                self._inflight_steps[sid] = left
            else:
                self._inflight_steps.pop(sid, None)
            s = self.sequences.get(sid)
            if s is None or s.done:
                continue  # finished/vanished while this block was in flight
            n0 = len(s.tokens)
            # The open decode span, captured BEFORE the accept loop can
            # close it (EOS mid-block): the block's span child must attach
            # to the span that was live while the block ran.
            dspan = s.decode_span
            try:
                for j in range(int(budgets[lane])):
                    self._accept_token(s, int(toks[lane, j]))
                    if s.done:
                        break
            except Exception as e:  # noqa: BLE001 - raising stream callback
                if first_exc is None:
                    first_exc = e
                s.done = True
                s.finish_reason = s.finish_reason or "error"
            finally:
                accepted = s.tokens[n0:]
                out[sid] = accepted
                produced += len(accepted)
                if dspan is not None and accepted:
                    # Span per pulled block: dispatch -> pull. Blocks of
                    # one sequence overlap under pipeline_depth > 0, which
                    # is the point — the trace shows the pipelining.
                    t0 = time.perf_counter()
                    dspan.child(
                        "decode_block", t_disp, t0,
                        tokens=len(accepted), tick=tick_id,
                    )
                    self._summed("account", t0)
                if s.done:
                    # Roll pre-booked pages back to written content. Any
                    # still-in-flight dispatch may keep writing to the freed
                    # pages, but device execution is in dispatch order: a
                    # future owner's writes always land after the stale
                    # ones, so reuse is safe without draining.
                    self.alloc.truncate(sid, self._host_written(s))
                    self._free_lane(sid)
        t0 = time.perf_counter()
        perf.record_metric("engine.decode_tokens", produced, "tok")
        self._observe_occupancy()
        obs.add_part("commit", "account", time.perf_counter() - t0)
        if first_exc is not None:
            raise first_exc
        return out

    def step(self, seq_ids: list[int] | None = None) -> dict[int, int]:
        """One decode step over up to max_batch_size running sequences.
        Returns {seq_id: new_token} for sequences that advanced."""
        with self.lock:
            self._async_settle()
            # Host-gap continuity ends here: a non-mixed dispatch between
            # two mixed ticks would otherwise count as a giant "gap".
            self._mixed_gap_stamp = None
            targets = (
                list(self.sequences) if seq_ids is None else list(seq_ids)
            )
            if any(sid in self._lane_of for sid in targets):
                self._flush_and_invalidate()
            running = [
                s for s in self.sequences.values() if not s.done
            ] if seq_ids is None else [
                self.sequences[i] for i in seq_ids if not self.sequences[i].done
            ]
            running = running[: self.cfg.max_batch_size]
            if not running:
                return {}
            B = self.cfg.max_batch_size
            # Account for the token each sequence is about to write. A
            # sequence that cannot grow (pool exhausted or per-seq page cap)
            # is finished as truncated instead of killing the whole step.
            grown: list[Sequence] = []
            try:
                for s in running:
                    try:
                        self.alloc.extend(s.seq_id, 1)
                        grown.append(s)
                        continue
                    except OutOfPages:
                        pass
                    # Pool dry — possibly only transiently: the pipeline's
                    # in-flight blocks pre-book pages that their pulls roll
                    # back. Drain before declaring the sequence truncated.
                    while self._inflight:
                        self._pull_oldest()
                    try:
                        self.alloc.extend(s.seq_id, 1)
                        grown.append(s)
                    except OutOfPages:
                        s.done = True
                        s.finish_reason = "length"
                        obs.PREEMPTIONS.inc()
                        obs.flight.record("preemption", seq_id=s.seq_id)
                        log.warning(
                            "seq %d truncated: KV page budget exhausted",
                            s.seq_id,
                        )
            except BaseException:
                # The drain can re-raise a stream-callback exception. Undo
                # the +1 bookings made so far — they are for a token this
                # aborted step will never dispatch; leaving them would put
                # an unwritten hole inside the attended window next step.
                for s in grown:
                    if not s.done:
                        self.alloc.truncate(
                            s.seq_id, self.alloc.length(s.seq_id) - 1
                        )
                raise
            # A mid-loop pipeline drain can finish earlier-grown sequences
            # (EOS/stop in a pulled block); they must not decode further.
            running = [s for s in grown if not s.done]
            if not running:
                return {}
            ids: list[int | None] = [s.seq_id for s in running]
            ids += [None] * (B - len(ids))
            for s in running:
                at = self.alloc.length(s.seq_id)
                self.alloc.note_pass(s.seq_id, at - 1, at)
            self._count_scan(len(running), B)
            table, lengths, active = self.alloc.batch_views(ids, B)
            # lengths now include the new token; decode wants the write
            # offset (tokens already present before this step).
            write_at = lengths.copy()
            for i, s in enumerate(running):
                write_at[i] = lengths[i] - 1
            tokens = np.zeros((B,), np.int32)
            for i, s in enumerate(running):
                tokens[i] = s.tokens[-1] if s.tokens else self.tokenizer.bos_id
            slots = running + [None] * (B - len(running))
            temps, top_k, top_p, mask = self._sampling_arrays(slots, B)
            bias = self._bias_array(slots, B)
            want_lp = any(s.params.logprobs for s in running)
            chosen_lp = top_ids = top_lps = None
            t_step = time.perf_counter()
            with self.mesh_ctx():
                # split under the mesh like warmup's, or its eager helper
                # programs recompile on the first serving-window call.
                self._sample_key, sub = jax.random.split(self._sample_key)
                args = (
                    self.params,
                    jnp.asarray(tokens),
                    jnp.asarray(write_at),
                    self.cache,
                    jnp.asarray(table),
                    jnp.asarray(active),
                    sub,
                    jnp.asarray(temps),
                    jnp.asarray(top_k),
                    jnp.asarray(top_p),
                    None if mask is None else jnp.asarray(mask),
                    None if bias is None else jnp.asarray(bias),
                )
                if want_lp:
                    sampled, chosen_lp, top_ids, top_lps, self.cache = (
                        self._decode_sample_lp_jit(*args)
                    )
                    chosen_lp = np.asarray(chosen_lp)
                    top_ids = np.asarray(top_ids)
                    top_lps = np.asarray(top_lps)
                else:
                    sampled, self.cache = self._decode_sample_jit(*args)
            sampled = np.asarray(sampled)
            from .decode_loop import record_dispatch

            step_ctx = int(sum(
                int(write_at[i]) + 1 for i in range(len(running))
            ))
            record_dispatch(
                "single", rows=len(running), steps=1,
                attr=self.attr,
                attr_kw=dict(
                    q_tokens=len(running),
                    kv_read_tokens=step_ctx,
                    kv_write_tokens=len(running),
                    attn_q_ctx=step_ctx,
                    measured_s=time.perf_counter() - t_step,
                ),
            )
            obs.flight.record(
                "dispatch", op="decode_single",
                seq_ids=[s.seq_id for s in running],
            )
            out: dict[int, int] = {}
            first_exc: BaseException | None = None
            with self._accepting():
                for i, s in enumerate(running):
                    tok = int(sampled[i])
                    dspan = s.decode_span
                    if s.params.logprobs:
                        n = s.params.top_logprobs
                        s.logprob_data.append({
                            "logprob": float(chosen_lp[i]),
                            "top": [
                                (int(top_ids[i, j]), float(top_lps[i, j]))
                                for j in range(min(n, top_ids.shape[1]))
                            ],
                        })
                    try:
                        self._accept_token(s, tok)
                    except Exception as e:  # noqa: BLE001 - raising stream cb
                        # Isolate the disconnected client: only ITS sequence
                        # errors (same contract as _pull_oldest); the rest of
                        # the batch keeps its tokens.
                        if first_exc is None:
                            first_exc = e
                        s.done = True
                        s.finish_reason = s.finish_reason or "error"
                        self.alloc.truncate(s.seq_id, self._host_written(s))
                    # _accept_token appends before the callback runs, so
                    # even an errored sequence's token is in seq.tokens (and
                    # in what finish() returns) — report it, matching
                    # _pull_oldest.
                    out[s.seq_id] = tok
                    if dspan is not None:
                        dspan.child(
                            "decode_step", t_step, time.perf_counter(),
                            tokens=1,
                        )
            get_perf_stats().record_metric("engine.decode_tokens", len(running), "tok")
            self._observe_occupancy()
            if first_exc is not None:
                raise first_exc
            return out

    def step_block(self, seq_ids: list[int] | None = None) -> dict[int, list[int]]:
        """Advance running sequences by up to ``cfg.decode_block`` tokens per
        device dispatch, keeping ``cfg.pipeline_depth`` dispatches in flight:
        the decode loop state (last token, write offset, EOS flags, PRNG key)
        lives ON DEVICE (decode_loop.decode_block_carry), so block k+1 is
        enqueued before block k's tokens are pulled and the pull RTT overlaps
        device compute. Tokens are therefore reported up to ``depth`` blocks
        after they were generated.

        Rows with a constrained-decoding mask advance one fused step per
        call instead (masks are host-computed per token); unconstrained rows
        in the same batch still pipeline. Returns {seq_id: accepted tokens}
        for sequences that advanced this call."""
        with self.lock:
            self._async_settle()
            self._mixed_gap_stamp = None  # see step(): gap continuity ends
            with obs.phase("plan", part="rows"):
                running = [
                    s for s in self.sequences.values() if not s.done
                ] if seq_ids is None else [
                    self.sequences[i] for i in seq_ids if not self.sequences[i].done
                ]
                running = running[: self.cfg.max_batch_size]
                block = self.cfg.decode_block
                # Constrained rows whose FSM fits the device-table budget ride
                # the PIPELINED block: the grammar mask is a [B, V] table
                # gather per step and the DFA state advances on device — no
                # host sync per token (SURVEY §7's hard part). One shared
                # table set per dispatch; seated fsm lanes pin the choice, and
                # rows with a different schema fall back to host stepping.
                from .constrained import JsonConstraint

                fsm_obj = None
                for sid in self._lanes:
                    s = self.sequences.get(sid) if sid is not None else None
                    if (
                        s is not None and not s.done
                        and isinstance(s.mask_fn, JsonConstraint)
                    ):
                        fsm_obj = s.mask_fn.fsm
                        break

                def fsm_ok(s):
                    nonlocal fsm_obj
                    if (
                        not isinstance(s.mask_fn, JsonConstraint)
                        or s.params.logprobs
                        or self._needs_bias(s)
                        or s.mask_fn.fsm.dense_tables() is None
                    ):
                        return False
                    if fsm_obj is None:
                        fsm_obj = s.mask_fn.fsm
                        return True
                    return s.mask_fn.fsm is fsm_obj

                # Host-stepped rows: non-FSM constrained masks need a
                # host-computed logits mask per token; logprob rows need
                # per-token device pulls the pipelined block does not surface;
                # biased rows need the bias rebuilt per token.
                def hosted(s):
                    return (
                        (s.mask_fn is not None and not fsm_ok(s))
                        or s.params.logprobs
                        or self._needs_bias(s)
                    )

                masked = [s for s in running if hosted(s)]
                plain = [s for s in running if not hosted(s)]
            if running and (block <= 1 or (masked and not plain)):
                return {
                    sid: [tok]
                    for sid, tok in self.step(
                        [s.seq_id for s in running]
                    ).items()
                }
            out: dict[int, list[int]] = {}
            if masked:
                # Mixed batch: constrained rows need a host-computed logits
                # mask per token, so they advance one fused step per call
                # (step() does not flush the pipeline for lane-less masked
                # rows) while the unconstrained rows pipeline underneath.
                out.update({
                    sid: [tok]
                    for sid, tok in self.step(
                        [s.seq_id for s in masked]
                    ).items()
                })
                plain = [s for s in plain if not s.done]
            B = self.cfg.max_batch_size
            with obs.phase("plan", part="lanes"):
                # Lane sync: free lanes of finished sequences, then seat
                # newly running ones. A lane holds its sequence for its
                # whole life, so the device carry stays valid across
                # dispatches.
                for lane, sid in enumerate(self._lanes):
                    if sid is None:
                        continue
                    s = self.sequences.get(sid)
                    if s is None or s.done:
                        self._lanes[lane] = None
                        self._lane_of.pop(sid, None)
                override = np.zeros((B,), bool)
                ov_tok = np.zeros((B,), np.int32)
                ov_at = np.zeros((B,), np.int32)
                ov_fsm = np.zeros((B,), np.int32)  # 0 = FREE sentinel row
                for s in plain:
                    if s.seq_id in self._lane_of:
                        continue
                    try:
                        lane = self._lanes.index(None)
                    except ValueError:
                        break  # more running sequences than lanes: they wait
                    self._lanes[lane] = s.seq_id
                    self._lane_of[s.seq_id] = lane
                    override[lane] = True
                    ov_tok[lane] = (
                        s.tokens[-1] if s.tokens else self.tokenizer.bos_id)
                    # Invariant at (re)seating: alloc.length == written tokens.
                    ov_at[lane] = self.alloc.length(s.seq_id)
                    if isinstance(s.mask_fn, JsonConstraint):
                        # Walk the DFA over what this row generated so far;
                        # +1 because device-table row 0 is the FREE sentinel.
                        fsm = s.mask_fn.fsm
                        st = fsm.dfa.start
                        for t in s.tokens:
                            if t != fsm.eos_id:
                                st = fsm.advance(st, t)
                        ov_fsm[lane] = st + 1
                # Book pages for up to one block per lane; budgets account for
                # still-in-flight dispatches so max_tokens is never overshot.
                # Seated lanes OUTSIDE the caller's seq_ids filter keep their
                # device carry but get no budget — they do not advance.
                requested = {s.seq_id for s in plain}
                alive = np.zeros((B,), bool)
                budgets = np.zeros((B,), np.int32)
                lane_seqs: list[int | None] = [None] * B
                for lane, sid in enumerate(self._lanes):
                    if sid is None:
                        continue
                    if sid not in requested:
                        alive[lane] = True
                        lane_seqs[lane] = sid
                        continue
                    s = self.sequences[sid]
                    want = min(
                        block,
                        s.params.max_tokens - len(s.tokens)
                        - self._inflight_steps.get(sid, 0),
                    )
                    if want <= 0:
                        # Budget fully covered by in-flight blocks: keep the
                        # lane seated, dispatch nothing for it.
                        alive[lane] = True
                        lane_seqs[lane] = sid
                        continue
                    got = self.alloc.extend_upto(sid, want)
                    if got == 0:
                        # Page pool dry. Before killing the row, drain the
                        # pipeline: its in-flight blocks may hold legitimately
                        # generated tokens for this sequence (discarding them
                        # would truncate the response early), and their pulls
                        # roll back other finished rows' pages — which can make
                        # this extend succeed after all.
                        while self._inflight:
                            _merge_pulls(out, self._pull_oldest())
                        # The drain may have finished sequences whose lanes
                        # were already budgeted earlier in this loop — zero
                        # them so the dispatch does not resurrect dead rows.
                        for lx, sx in enumerate(lane_seqs):
                            if sx is not None and (
                                sx not in self.sequences
                                or self.sequences[sx].done
                            ):
                                alive[lx] = False
                                budgets[lx] = 0
                                lane_seqs[lx] = None
                        if s.done:
                            continue  # drained blocks finished it (EOS/stop)
                        got = self.alloc.extend_upto(sid, want)
                    if got == 0:
                        s.done = True
                        s.finish_reason = "length"
                        obs.PREEMPTIONS.inc()
                        obs.flight.record("preemption", seq_id=sid)
                        self.alloc.truncate(sid, self._host_written(s))
                        self._free_lane(sid)
                        override[lane] = False
                        log.warning(
                            "seq %d truncated: KV page budget exhausted", sid
                        )
                        continue
                    alive[lane] = True
                    budgets[lane] = got
                    lane_seqs[lane] = sid
                    at = self.alloc.length(sid)
                    self.alloc.note_pass(sid, at - got, at, each_token=True)
            if not budgets.any():
                # Nothing to dispatch; a pull still guarantees progress.
                if self._inflight:
                    _merge_pulls(out, self._pull_oldest())
                return out
            with obs.phase("plan", part="account"):
                self._count_context(
                    np.asarray(
                        [self.alloc.length(sid) if budgets[lane] else 0
                         for lane, sid in enumerate(lane_seqs)], np.int64),
                    budgets, block)
                self._count_scan(int(budgets.sum()), B * block)
            with self._building_arrays():
                table, _, _ = self.alloc.batch_views(lane_seqs, B)
                slots = [
                    self.sequences.get(sid) if sid is not None else None
                    for sid in lane_seqs
                ]
                temps, top_k, top_p, _ = self._sampling_arrays(slots, B)
                greedy = bool(np.all(temps <= 0.0))
                # A constrained row that failed to get a lane (batch full) must
                # not force FSM tables on a dispatch where no SEATED row is
                # constrained — it isn't advancing anyway. Re-derive from what
                # actually seated.
                if fsm_obj is not None and not any(
                    isinstance(
                        getattr(self.sequences.get(sid), "mask_fn", None),
                        JsonConstraint,
                    )
                    for sid in self._lanes
                    if sid is not None
                ):
                    fsm_obj = None
                if self._carry is None:
                    # Under mesh_ctx like every other eager helper: the zeros/
                    # split programs recompile per mesh-context depth
                    # otherwise.
                    with self.mesh_ctx():
                        # Fork the decode-loop PRNG stream off the admission
                        # stream so per-step sampling never reuses an
                        # admission key.
                        self._sample_key, carry_key = jax.random.split(
                            self._sample_key
                        )
                        # Distinct arrays: the donated args must be distinct
                        # buffers (donating the same one twice is an error).
                        self._carry = (
                            jnp.zeros((B,), jnp.int32),
                            jnp.zeros((B,), jnp.int32),
                            jnp.zeros((B,), bool),
                            jnp.zeros((B,), jnp.int32),  # device FSM (0=free)
                            carry_key,
                        )
                c_tok, c_at, c_eos, c_fsm, c_key = self._carry
                perf = get_perf_stats()
                if fsm_obj is not None:
                    fsm_mask_d, fsm_dest_d = self._fsm_device_tables(fsm_obj)
                else:
                    fsm_mask_d = fsm_dest_d = None
            ticket = self.step_clock.enqueue()
            tick_id, t_disp, _ = ticket
            with obs.phase("dispatch", tick=tick_id), self.mesh_ctx():
                with obs.phase("dispatch", part="place"):
                    (override_d, ov_tok_d, ov_at_d, alive_d, budgets_d,
                     table_d) = (
                        jnp.asarray(a) for a in (
                            override, ov_tok, ov_at, alive, budgets, table))
                    temps_d, top_k_d, top_p_d, ov_fsm_d = (
                        jnp.asarray(a)
                        for a in (temps, top_k, top_p, ov_fsm))
                with obs.phase("dispatch", part="call"), \
                        annotate("engine.decode_block"):
                    toks, self.cache, self._carry = self._decode_pipeline_jit(
                        self.params,
                        c_tok, c_at, c_eos, c_key,
                        override_d,
                        ov_tok_d,
                        ov_at_d,
                        alive_d,
                        budgets_d,
                        self.cache,
                        table_d,
                        temps_d,
                        top_k_d,
                        top_p_d,
                        greedy=greedy,
                        fsm_mask=fsm_mask_d,
                        fsm_dest=fsm_dest_d,
                        carry_fsm=c_fsm,
                        ov_fsm=ov_fsm_d,
                    )
            with obs.phase("plan", part="account"):
                perf.record_metric(
                    "engine.block_dispatch",
                    (time.perf_counter() - t_disp) * 1e3, "ms",
                )
                from .decode_loop import record_dispatch

                # Attribution: each budgeted lane writes `b` tokens, step
                # j attending start+j+1 positions (exact causal sum); the
                # scan streams the weights once per SCAN STEP regardless
                # of how few lanes carry budget (inactive lanes ride the
                # stream).
                attr_q = attr_read = 0
                for lane, sid in enumerate(lane_seqs):
                    b = int(budgets[lane])
                    if sid is None or b == 0:
                        continue
                    s0 = max(0, self.alloc.length(sid) - b)
                    attr_q += b
                    attr_read += b * s0 + b * (b + 1) // 2
                record_dispatch(
                    "block",
                    rows=int(np.count_nonzero(budgets)),
                    steps=int(budgets.max()),
                    attr=self.attr,
                    attr_kw=dict(
                        weight_streams=self.cfg.decode_block,
                        q_tokens=attr_q,
                        kv_read_tokens=attr_read,
                        kv_write_tokens=attr_q,
                        attn_q_ctx=attr_read,
                    ),
                )
                obs.flight.record(
                    "dispatch", op="decode_block",
                    seq_ids=[sid for sid, b in zip(lane_seqs, budgets)
                             if sid is not None and b],
                    steps=int(budgets.max()), tick=tick_id,
                )
            with obs.phase("plan", part="book"):
                self._inflight.append((
                    toks, lane_seqs, budgets, ticket, ("decode_block", block),
                ))
                for sid, b in zip(lane_seqs, budgets):
                    if sid is not None and b:
                        self._inflight_steps[sid] = (
                            self._inflight_steps.get(sid, 0) + int(b)
                        )
            while len(self._inflight) > self.cfg.pipeline_depth:
                _merge_pulls(out, self._pull_oldest())
            return out

    # -- hierarchical KV tier (serving/offload) ------------------------------
    def _spill_page(self, page: int, chain_tokens: list[int]) -> None:
        """PageAllocator eviction hook: enqueue a device->host copy of the
        page being dropped, keyed by its token chain, so the content
        survives in the host pool. Runs under the engine lock (eviction
        happens inside allocate/extend); the gather is dispatched here —
        ordered before any later write to the recycled page — and pulled
        at the next flush point."""
        if self.offload is not None:
            self.offload.spill(self.cache, [(page, chain_tokens)])

    def offload_flush(self) -> int:
        """Pull pending device->host page copies into the host pool (the
        double buffer's drain side). Cheap no-op when nothing is pending;
        the scheduler calls this on idle ticks and admission calls it
        before matching."""
        if self.offload is None:
            return 0
        with self.lock:
            return self.offload.flush()

    def prefix_digests(self, cap: int | None = None) -> list[str]:
        """Compact prefix digest of this replica's cached state for the
        fleet registry: hex chain keys (offload/pool.chain_key_hex) of
        every HBM-trie-resident page chain plus every host-pool page. The
        router scores a prompt's longest-cached-prefix affinity against
        this set and indexes it into the fleet page directory; ``cap``
        bounds the advertisement (explicit arg > env
        ``OPSAGENT_FLEET_DIGEST_CAP`` > 4096; newest content wins by
        iteration order — over-cap replicas just under-advertise, which
        only costs affinity/fault-in hits, never correctness). Sets
        ``digests_truncated()`` so the registry snapshot can surface
        replicas whose advertisement is clipped."""
        from .offload.pool import chain_key_hex

        if cap is None:
            try:
                cap = int(os.environ.get("OPSAGENT_FLEET_DIGEST_CAP", ""))
            except ValueError:
                cap = 0
            if cap <= 0:
                cap = 4096
        with self.lock:
            keys = [chain_key_hex(c) for c in self.alloc.trie_chains()]
        if self.offload is not None:
            keys.extend(self.offload.pool.digests())
        self._digests_truncated = len(keys) > cap
        if self._digests_truncated:
            keys = keys[-cap:]
        return keys

    def digests_truncated(self) -> bool:
        """Whether the last ``prefix_digests`` advertisement was clipped
        by the digest cap (registry snapshot: ``digest_truncated``)."""
        return self._digests_truncated

    def fault_in_prefix(
        self, prompt_ids: list[int], request_id: str = ""
    ) -> int:
        """Fleet-global KV fault-in (tier 3): when the usable prefix of
        ``prompt_ids`` misses the HBM trie AND the host pool, ask the
        fleet page directory who owns the missing chain and fetch it
        peer-to-peer into the host pool (fleet/pagestore.py), so the
        admission's ordinary host restore lands it. Probes under the
        engine lock (cheap reads), fetches OUTSIDE it. Returns pages
        landed; 0 on any miss/failure — never raises into admission.
        ``request_id`` tags the fault-in flight events with the journey
        this admission serves (fleet timeline stitching)."""
        if self.pagestore is None or self.offload is None:
            return 0
        try:
            usable = prompt_ids[: len(prompt_ids) - 1]
            total = len(usable) // self.cfg.page_size
            if total == 0:
                return 0
            with self.lock:
                self.offload.flush()
                matched = len(self.alloc.match_prefix(usable))
            covered = self.offload.pool.coverage(
                usable, start_page=matched
            )
            if matched + covered >= total:
                return 0  # local tiers cover it — no fetch
            return self.pagestore.fault_in(
                usable, start_page=matched + covered,
                request_id=request_id,
            )
        except Exception:  # noqa: BLE001 - NEVER raises into admission
            log.exception("page fault-in probe failed; re-prefilling")
            return 0

    def replicate_chain(self, token_ids: list[int]) -> int:
        """Non-destructive export support: copy this token chain's
        trie-resident pages into the host pool WITHOUT evicting them
        (spill is a pure copy; eviction is a separate step), so a peer
        fault-in can pack the chain while local sessions keep decoding
        on it. Contrast ``park_chain``, which frees the HBM pages.
        Returns pages newly copied (0 = already pool-resident or not
        trie-resident)."""
        if self.offload is None:
            return 0
        from .offload.pool import chain_key_hex

        with self.lock:
            self.offload.flush()
            pages = self.alloc.match_prefix(token_ids)
            if not pages:
                return 0
            P = self.cfg.page_size
            have = set(self.offload.pool.digests())
            chains = [
                (pg, token_ids[: (i + 1) * P])
                for i, pg in enumerate(pages)
                if chain_key_hex(token_ids[: (i + 1) * P]) not in have
            ]
            if not chains:
                return 0
            self.offload.spill(self.cache, chains, trigger="replicate")
            return self.offload.flush()

    def park_chain(self, token_ids: list[int]) -> int:
        """Tool-time parking: free the HBM pages holding this token
        history's KV (the session's trie-resident state) after copying
        them to the host pool. Called while the session's ReAct loop
        blocks on tool execution — the multi-second window where the
        pages only deny admission to queued prompts. Returns tokens
        parked (0 when offload is off or nothing was evictable)."""
        if self.offload is None:
            return 0
        with self.lock:
            pages = self.alloc.match_prefix(token_ids)
            if not pages:
                return 0
            n = self.alloc.evict_chain(pages)
            if n:
                obs.OFFLOAD_PARKS.inc(trigger="tool")
                obs.flight.record(
                    "park", trigger="tool", pages=n,
                    tokens=n * self.cfg.page_size,
                )
                self._observe_occupancy()
            return n * self.cfg.page_size

    def park_sequence(self, seq_id: int) -> "Sequence | None":
        """Pressure parking: offload a LIVE sequence's written pages to
        the host pool and free ALL its HBM state, returning the tokens it
        generated so far. The caller (scheduler) re-queues the request
        with the salvage folded into its prompt — exactly the
        slice-restart salvage flow — and the re-admission restores the
        pages from the host pool instead of re-prefilling. Returns the
        parked Sequence (host-side state: tokens, logprob_data), or None
        (nothing parked) when the sequence turned out to be finished by
        the time the pipeline settled — the caller reaps it normally."""
        if self.offload is None:
            raise RuntimeError("park_sequence requires the offload tier")
        with self.lock:
            self._async_settle()
            # Settle pipelined decode first: in-flight blocks may still
            # append tokens to this sequence (and their pulls roll page
            # bookings back to written content). Stream-callback raises
            # belong to their own (now-errored) rows — keep draining.
            while self._inflight or self._lane_of:
                try:
                    self._flush_and_invalidate()
                except Exception:  # noqa: BLE001
                    log.exception(
                        "stream callback raised while settling pipelined "
                        "state for parking; row isolated"
                    )
            seq = self.sequences.get(seq_id)
            if seq is None or seq.done:
                return None  # finished mid-drain: reap, don't park
            seq = self.sequences.pop(seq_id)
            written = seq.prompt_ids + seq.tokens[:-1]
            P = self.cfg.page_size
            pages = self.alloc.pages_of(seq_id)
            chains = [
                (pages[i], written[: (i + 1) * P])
                for i in range(min(len(written) // P, len(pages)))
            ]
            if chains:
                self.offload.spill(self.cache, chains, trigger="pressure")
            # Plain free (no trie donation): the content now lives in the
            # host tier; keeping an HBM copy would defeat the parking.
            self.alloc.free(seq_id)
            obs.OFFLOAD_PARKS.inc(trigger="pressure")
            obs.flight.record(
                "park", trigger="pressure", seq_id=seq_id,
                pages=len(chains), tokens=len(chains) * P,
                generated=len(seq.tokens),
            )
            if seq.decode_span is not None:
                obs.attribution.record_goodput(
                    seq.decode_span.duration_s(), "decode_active"
                )
                seq.decode_span.close(
                    tokens=len(seq.tokens), finish_reason="parked"
                )
                seq.decode_span = None
            self._observe_occupancy()
            return seq

    def abort_request(self, seq_id: int) -> None:
        """Abandon a sequence that is still in the prefilling state (e.g.
        scheduler shutdown): free its pages and drop its host state. No-op
        for ids the engine no longer tracks."""
        with self.lock:
            if self._prefilling.pop(seq_id, None) is None:
                return
            self.sequences.pop(seq_id, None)
            self.alloc.free(seq_id)

    def drain(self) -> dict[int, list[int]]:
        """Pull every in-flight decode dispatch and fold the tokens into
        host state. Call before reading final sequence state outside the
        step loop (benchmarks, shutdown); the step loop itself drains
        incrementally."""
        with self.lock:
            out: dict[int, list[int]] = {}
            if self._async.pending:
                # Decode tokens fold into this drain's result; prefill
                # completions stay buffered for async_take_results (the
                # scheduler must still learn about them).
                _merge_pulls(out, self._async.drain_decode())
            while self._inflight:
                _merge_pulls(out, self._pull_oldest())
            return out

    def finish(self, seq_id: int) -> list[int]:
        """Release resources; returns the generated tokens. Full pages are
        donated to the prefix trie keyed by their exact token history (the
        cache holds prompt + generated[:-1]: the last sampled token is never
        written back by a decode step)."""
        with self.lock:
            seq = self.sequences.pop(seq_id)
            self._ffwd_noted.discard(seq_id)
            self.alloc.free(seq_id, tokens=seq.prompt_ids + seq.tokens[:-1])
            t0 = time.perf_counter()    # the rest observes: reap's account
            obs.flight.record(
                "finish", seq_id=seq_id, tokens=len(seq.tokens),
                finish_reason=seq.finish_reason,
                request_id=obs.flight.request_id_of(seq.trace),
            )
            if seq.decode_span is not None:
                # Aborted/errored sequences can reach finish() with the
                # decode span still open.
                obs.attribution.record_goodput(
                    seq.decode_span.duration_s(), "decode_active"
                )
                seq.decode_span.close(
                    tokens=len(seq.tokens), finish_reason=seq.finish_reason
                )
                seq.decode_span = None
            self._observe_occupancy()
            obs.add_part("reap", "account", time.perf_counter() - t0)
            return seq.tokens

    # -- convenience (tests / bench) ----------------------------------------
    def generate(
        self,
        prompts: list[list[int]],
        sampling: SamplingParams | None = None,
    ) -> list[list[int]]:
        """Synchronous batch generation with continuous decode stepping."""
        ids = [self.add_request(p, sampling) for p in prompts]
        pending = {i for i in ids if not self.sequences[i].done}
        while pending:
            self.step_block(sorted(pending))
            pending = {i for i in pending if not self.sequences[i].done}
        return [self.finish(i) for i in ids]
