"""Model family configurations.

The serving engine hosts open-weights instruct models in the llama
architecture family (Llama-3, Qwen2.5 — GQA + RoPE + SwiGLU + RMSNorm) and,
via ``moe``, DeepSeek-style mixture-of-experts variants. Presets carry the
published architecture hyperparameters; weights are loaded from safetensors
checkpoints or randomly initialized (tests/benchmarks).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    num_experts_per_token: int = 2
    num_shared_experts: int = 0
    expert_intermediate_size: int = 0  # 0 = use model intermediate_size
    # Router combine-weight semantics, matching the HF config fields of the
    # same names. DeepSeek-MoE-16B / V2-Lite / Qwen2-MoE checkpoints ship
    # norm_topk_prob=false (combine with raw softmax probabilities);
    # renormalizing for them scales expert outputs by 1/sum(top-k probs)
    # (~1.5-3x at k=6 of 64) and corrupts generation. DeepSeek-V3 ships
    # norm_topk_prob=true with routed_scaling_factor=2.5.
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    # Router scoring (HF scoring_func): "softmax" (DeepSeek-MoE/V2) or
    # "sigmoid" (V3). Sigmoid scoring pairs with the noaux_tc topk_method:
    # a per-expert e_score_correction_bias (loaded from the checkpoint)
    # is added for SELECTION only; combine weights use the uncorrected
    # sigmoid scores.
    scoring_func: str = "softmax"
    # Group-limited top-k (HF n_group/topk_group): experts partition into
    # n_group groups; only the topk_group best groups are eligible.
    # Group ranking follows scoring_func: sigmoid (V3 noaux_tc) ranks by
    # top-2 sum, softmax (V2 group_limited_greedy) by group max. 1/1
    # disables.
    n_group: int = 1
    topk_group: int = 1
    # Grouped-dispatch policy. Below the token threshold (decode steps,
    # tiny batches) the all-experts scan runs instead: with T*k >= E every
    # expert's weights stream from HBM once either way, so the scan is
    # bandwidth-optimal and has no drop risk. Above it (prefill/training)
    # tokens are dispatched into per-expert capacity buckets of
    # ceil(T*k/E * capacity_factor) slots — expert FLOPs scale with top-k,
    # not num_experts. 0 disables grouped dispatch entirely.
    grouped_dispatch_min_tokens: int = 512
    capacity_factor: float = 2.0
    # Experts at one chip's share of an expert-parallel deployment:
    # ``num_experts`` stays the count HELD here (the leading axis of the
    # expert stacks), ``router_experts`` is the router's published width
    # (0 = the router is as wide as what is held: the whole layer lives
    # here) and ``first_expert`` the global id of the first one held. The
    # router scores and ranks all ``router_experts``; the layer computes the
    # chosen experts it holds and leaves out what the absent ones would add.
    router_experts: int = 0
    first_expert: int = 0

    @property
    def router_width(self) -> int:
        return self.router_experts or self.num_experts


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2/V3): low-rank q and kv
    projections with a decoupled per-head-SHARED RoPE part. Two serving
    layouts: ``latent_cache=False`` materializes per-head k/v onto the
    standard paged cache (v zero-padded to the qk head dim so every
    attention path is shared); ``latent_cache=True`` stores the compressed
    per-token latent and decodes in the weight-absorbed MQA form — the
    memory/bandwidth win that motivates MLA."""

    q_lora_rank: int = 0           # 0 = full-rank q projection (V2-Lite)
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # Serve with the COMPRESSED latent cache: pages hold one
    # (kv_lora_rank + qk_rope_head_dim)-dim latent per token instead of
    # per-head k/v — the point of MLA (V3: 576 vs 49152 floats/token,
    # ~85x less KV memory/bandwidth). Decode absorbs the kv
    # up-projection into the query (per-head latent queries, MQA-style
    # attention over the shared latent); prefill attends materialized
    # and writes latents. False = uncompressed per-head cache.
    latent_cache: bool = False

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def page_dim(self) -> int:
        """Width of a token's row in the latent pages: the latent padded
        with zeros to whole 128-lane tiles (576 -> 640). An array whose
        minor axis is off the lanes is held by the TPU in a layout of the
        compiler's choosing (at ``[L, N, P, 576]``: pages innermost), and
        every step program then copied the whole cache to row-major at its
        entry and back at its exit (compile, PR 30 and PR 40)."""
        return -(-self.latent_dim // 128) * 128


@dataclass(frozen=True)
class LinearAttnConfig:
    """Gated delta-rule linear attention: a float32 state ``[num_heads,
    key_head_dim, value_head_dim]`` per sequence instead of pages, and a
    causal depthwise convolution of ``conv_kernel`` over the q/k/v streams
    (its last ``conv_kernel - 1`` inputs are state too). ``neg_eigval``
    doubles beta's range to (0, 2), which lets a state transition have
    negative eigenvalues. Two published layers differ in two ways:

    - ``decay``: how fine the decay is. ``"channel"``: one for every key
      channel of every head (Kimi Delta Attention; ``dt_bias`` is as wide
      as the keys). ``"head"``: one number a head (Gated DeltaNet;
      ``dt_bias`` is ``[num_heads]``).
    - ``gates``: ``"low_rank"``: the decay and the output gate are two
      low-rank projections of width ``gate_rank`` each, the output gate a
      sigmoid beside the per-head RMSNorm of the read-out (KDA).
      ``"full"``: the decay is one projection ``wa`` as wide as the decay,
      the output gate one full-rank projection ``wog``, and the gate is the
      silu of it (Gated DeltaNet's gated norm); ``gate_rank`` is unused."""

    num_heads: int = 4
    key_head_dim: int = 16
    value_head_dim: int = 16
    conv_kernel: int = 4
    gate_rank: int = 16
    neg_eigval: bool = True
    decay: str = "channel"
    gates: str = "low_rank"

    def __post_init__(self):
        if self.decay not in ("channel", "head"):
            raise ValueError(f"linear_attn.decay {self.decay!r}")
        if self.gates not in ("low_rank", "full"):
            raise ValueError(f"linear_attn.gates {self.gates!r}")

    @property
    def decay_size(self) -> int:
        """Decays of one token: a channel's each, or a head's."""
        return self.key_size if self.decay == "channel" else self.num_heads

    @property
    def key_size(self) -> int:
        return self.num_heads * self.key_head_dim

    @property
    def value_size(self) -> int:
        return self.num_heads * self.value_head_dim

    @property
    def conv_size(self) -> int:
        """Width of the convolved stream: q, k and v side by side."""
        return 2 * self.key_size + self.value_size


@dataclass(frozen=True)
class MambaConfig:
    """Mamba-1 selective state-space mixer (Jamba's): a float32 state
    ``[d_state, d_inner]`` per sequence instead of pages, a diagonal decay
    ``exp(dt A)`` with no delta update, and a causal depthwise convolution
    of ``d_conv`` over the ``d_inner`` stream in front of it (its last
    ``d_conv - 1`` inputs are state too). ``dt`` comes through a low-rank
    projection of width ``dt_rank``; Jamba norms ``dt``, ``B`` and ``C``
    (RMSNorm with a learned weight) before they are used."""

    d_inner: int = 128
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 8
    conv_bias: bool = True

    @property
    def x_proj_size(self) -> int:
        """Width of ``W_x``'s output: ``dt_low``, ``B`` and ``C``."""
        return self.dt_rank + 2 * self.d_state


# Kinds of token mixer a layer may have (``ModelConfig.mixer_period``).
MIXERS = ("attn", "linear", "mamba")
# Those that keep a recurrent state in the state slots.
STATE_MIXERS = ("linear", "mamba")


@dataclass(frozen=True)
class RopeScalingConfig:
    """Long-context rope frequency scaling (ops/rope.py implements the
    math). ``rope_type``: "llama3" (Llama-3.1's wavelength-banded
    interpolation) or "yarn" (DeepSeek-V2/V3; NTK-by-parts with mscale)."""

    rope_type: str
    factor: float
    original_max_position: int
    # llama3
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    # yarn
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int = 0  # 0 = hidden_size // num_heads
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    attn_bias: bool = False          # Qwen2-style q/k/v biases
    # Qwen3-style per-head RMSNorm on q and k (over head_dim, learned
    # [head_dim] weights, applied before RoPE).
    qk_norm: bool = False
    # With ``qk_norm``: the norm runs over the whole projection width
    # (Olmo2 / Olmo3: weights [q_size] and [kv_size]) before the heads are
    # split, instead of over each head.
    qk_norm_whole: bool = False
    tie_embeddings: bool = False
    max_position: int = 131072
    moe: Optional[MoEConfig] = None
    moe_layer_start: int = 0         # dense layers before the first MoE layer
    # DeepSeek-V2/V3 Multi-head Latent Attention. Constraints (validated
    # by models.llama.init_params): head_dim == mla.qk_head_dim and
    # num_kv_heads == num_heads (MLA has no GQA; the latent IS the
    # compression).
    mla: Optional[MLAConfig] = None
    # Long-context rope scaling; when set, max_position may cover the
    # scaled window (factor x original_max_position).
    rope_scaling: Optional[RopeScalingConfig] = None
    # The layer pattern, by its period: the kind of token mixer at each
    # position of one period ("attn" softmax attention over pages, "linear"
    # delta-rule linear attention over a recurrent state, "mamba" a
    # selective state-space scan over one); layer ``i`` has
    # ``mixer_period[i % len(mixer_period)]``. Empty = every layer "attn".
    # Equal at any depth that is a whole number of periods. The MLP's kind
    # stays ``moe`` / ``moe_layer_start``.
    mixer_period: tuple = ()
    linear_attn: Optional[LinearAttnConfig] = None
    mamba: Optional[MambaConfig] = None
    # softmax-attention output multiplied by sigmoid(x W_gate) before wo
    attn_output_gate: bool = False
    use_rope: bool = True            # False: no positional embedding (NoPE)
    # The Olmo2 block: ``h = x + norm(mixer(x))``, ``out = h + norm(mlp(h))``
    # (``attn_norm`` and ``mlp_norm`` follow the sublayer they are named
    # for, and nothing is normed before it). False: pre-norm.
    post_norm: bool = False

    def __post_init__(self):
        period = tuple(self.mixer_period)
        object.__setattr__(self, "mixer_period", period)
        if not period:
            return
        bad = [m for m in period if m not in MIXERS]
        if bad:
            raise ValueError(f"mixer_period {period}: unknown mixers {bad}")
        if "linear" in period and self.linear_attn is None:
            raise ValueError("mixer_period has linear layers: linear_attn unset")
        if "mamba" in period and self.mamba is None:
            raise ValueError("mixer_period has mamba layers: mamba unset")
        if "linear" in period and "mamba" in period:
            raise ValueError(
                "mixer_period mixes linear and mamba layers: the state slots "
                "hold one kind of recurrent state")
        if self.num_layers % len(period) or (
            self.moe is not None and self.moe_layer_start % len(period)
        ):
            raise ValueError(
                f"num_layers={self.num_layers} (and moe_layer_start) must be "
                f"whole periods of {len(period)} layers"
            )

    @property
    def period_(self) -> tuple:
        """The period as run: ("attn",) where none is configured."""
        return self.mixer_period or ("attn",)

    def mixer_of(self, layer: int) -> str:
        return self.period_[layer % len(self.period_)]

    def count_mixers(self, kind: str) -> int:
        """Layers of the whole model whose mixer is ``kind``."""
        p = self.period_
        return self.num_layers // len(p) * sum(1 for m in p if m == kind)

    @property
    def state_mixer(self) -> str:
        """The mixer whose layers keep a recurrent state beside (or instead
        of) pages: "linear", "mamba", or "" where no layer does."""
        return next((m for m in STATE_MIXERS if m in self.period_), "")

    @property
    def has_state(self) -> bool:
        """Some layer keeps a recurrent state beside (or instead of) pages."""
        return bool(self.state_mixer)

    @property
    def expert_share(self) -> bool:
        """The MLP of the MoE layers is ``llama._moe_share``: the router
        names its own width, of which this chip holds a share."""
        return self.moe is not None and self.moe.router_experts > 0

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def rope_dim_(self) -> int:
        """Dims RoPE rotates: the decoupled rope part under MLA, the whole
        head otherwise."""
        return self.mla.qk_rope_head_dim if self.mla else self.head_dim_

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim_

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim_

    def num_params(self, active: bool = False) -> int:
        """Parameters of the model this configuration describes: every
        layer by its mixer and its MLP, the router at its published width
        and the experts HELD here (a chip's share counts its share; the
        whole model is the preset with every expert held). ``active``
        counts what one token uses: its ``num_experts_per_token`` routed
        experts instead of all. MLA counts its published projections (the
        output projection over the heads' ``v_head_dim``, not the padded
        rows this program holds)."""
        d, f, v = self.hidden_size, self.intermediate_size, self.vocab_size
        attn = d * self.q_size + 2 * d * self.kv_size + self.q_size * d
        if self.mla is not None:
            a, H = self.mla, self.num_heads
            q = (d * a.q_lora_rank + a.q_lora_rank          # down, its norm
                 + a.q_lora_rank * H * a.qk_head_dim
                 if a.q_lora_rank else d * H * a.qk_head_dim)
            attn = (
                q + d * a.latent_dim + a.kv_lora_rank       # down, its norm
                + a.kv_lora_rank * H * (a.qk_nope_head_dim + a.v_head_dim)
                + H * a.v_head_dim * d)
        if self.attn_output_gate:
            attn += d * self.q_size
        if self.attn_bias:
            attn += self.q_size + 2 * self.kv_size
        if self.qk_norm:
            attn += (self.q_size + self.kv_size if self.qk_norm_whole
                     else 2 * self.head_dim_)
        linear = 0
        if self.linear_attn is not None:
            la = self.linear_attn
            if la.gates == "low_rank":      # downs and ups of both gates
                gates = (2 * d * la.gate_rank
                         + la.gate_rank * (la.decay_size + la.value_size))
            else:
                gates = d * (la.decay_size + la.value_size)
            linear = (
                d * la.conv_size + la.conv_kernel * la.conv_size   # q,k,v, conv
                + la.value_size * d                                # wo
                + gates
                + d * la.num_heads                                 # beta
                + la.num_heads + la.decay_size                     # A_log, dt_bias
                + la.value_head_dim                                # output norm
            )
        mamba = 0
        if self.mamba is not None:
            mc = self.mamba
            mamba = (
                d * 2 * mc.d_inner                                 # W_in
                + mc.d_conv * mc.d_inner                           # conv
                + (mc.d_inner if mc.conv_bias else 0)
                + mc.d_inner * mc.x_proj_size + mc.x_proj_size     # W_x, norms
                + mc.dt_rank * mc.d_inner + mc.d_inner             # W_dt, b_dt
                + mc.d_inner * mc.d_state + mc.d_inner             # A_log, D
                + mc.d_inner * d                                   # W_out
            )
        mixers = {"attn": attn, "linear": linear, "mamba": mamba}
        dense_mlp = 3 * d * f
        moe_mlp = 0
        if self.moe is not None:
            m = self.moe
            fe = m.expert_intermediate_size or f
            routed = m.num_experts_per_token if active else m.num_experts
            moe_mlp = (
                d * m.router_width
                + (m.router_width if m.scoring_func == "sigmoid" else 0)
                + 3 * d * fe * (routed + m.num_shared_experts)
            )
        total = 0
        for layer in range(self.num_layers):
            total += mixers[self.mixer_of(layer)]
            is_moe = self.moe is not None and layer >= self.moe_layer_start
            total += (moe_mlp if is_moe else dense_mlp) + 2 * d
        embed = v * d * (1 if self.tie_embeddings else 2)
        return total + embed + d


PRESETS: dict[str, ModelConfig] = {}


def _register(cfg: ModelConfig) -> ModelConfig:
    PRESETS[cfg.name] = cfg
    return cfg


# -- test/bench models ------------------------------------------------------
TINY_TEST = _register(
    ModelConfig(
        name="tiny-test",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        rope_theta=10000.0,
        # Generous window: the byte tokenizer spends ~3k tokens on the
        # agent system prompt, and admission now ENFORCES max_position.
        max_position=16384,
    )
)

# ~1B-class model for single-chip benchmarking (fits v5e 16GB in bf16 with
# room for KV pages).
BENCH_1B = _register(
    ModelConfig(
        name="bench-1b",
        vocab_size=128256,
        hidden_size=2048,
        intermediate_size=8192,
        num_layers=16,
        num_heads=32,
        num_kv_heads=8,
        rope_theta=500000.0,
    )
)

# ~3B-class single-chip bench model.
BENCH_3B = _register(
    ModelConfig(
        name="bench-3b",
        vocab_size=128256,
        hidden_size=3072,
        intermediate_size=8192,
        num_layers=28,
        num_heads=24,
        num_kv_heads=8,
        rope_theta=500000.0,
    )
)

# 8B-class bench model: exactly the Llama-3-8B architecture (the BASELINE
# north-star class). In bf16 its 16 GB of weights do NOT fit one 16 GB v5e
# chip — bench.py serves it with weight-only int8 (models.quant), 8 GB.
BENCH_8B = _register(
    ModelConfig(
        name="bench-8b",
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        rope_theta=500000.0,
        max_position=8192,
    )
)

# -- production model families (published architecture hyperparameters) -----
LLAMA3_8B = _register(
    ModelConfig(
        name="llama-3-8b-instruct",
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        rope_theta=500000.0,
        max_position=8192,
    )
)

LLAMA31_70B = _register(
    ModelConfig(
        name="llama-3.1-70b-instruct",
        vocab_size=128256,
        hidden_size=8192,
        intermediate_size=28672,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        rope_theta=500000.0,
        # 128k window via Llama-3.1's wavelength-banded rope scaling
        # (HF rope_scaling rope_type=llama3, factor 8 over the 8k
        # original window).
        rope_scaling=RopeScalingConfig(
            rope_type="llama3",
            factor=8.0,
            original_max_position=8192,
            low_freq_factor=1.0,
            high_freq_factor=4.0,
        ),
    )
)

QWEN25_7B = _register(
    ModelConfig(
        name="qwen2.5-7b-instruct",
        vocab_size=152064,
        hidden_size=3584,
        intermediate_size=18944,
        num_layers=28,
        num_heads=28,
        num_kv_heads=4,
        rope_theta=1000000.0,
        attn_bias=True,
        rms_norm_eps=1e-6,
        # Native window per the HF config (YaRN x4 to 128k is an opt-in
        # config edit upstream; add rope_scaling here to enable it).
        max_position=32768,
    )
)

QWEN25_72B = _register(
    ModelConfig(
        name="qwen2.5-72b-instruct",
        vocab_size=152064,
        hidden_size=8192,
        intermediate_size=29568,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        rope_theta=1000000.0,
        attn_bias=True,
        rms_norm_eps=1e-6,
        # Native window per the HF config (YaRN x4 to 128k is an opt-in
        # config edit upstream; add rope_scaling here to enable it).
        max_position=32768,
    )
)

# DeepSeek-MoE-16B (GQA + MoE; the pre-MLA DeepSeek generation).
DEEPSEEK_MOE_16B = _register(
    ModelConfig(
        name="deepseek-moe-16b",
        vocab_size=102400,
        hidden_size=2048,
        intermediate_size=10944,
        num_layers=28,
        num_heads=16,
        num_kv_heads=16,
        rope_theta=10000.0,
        moe=MoEConfig(
            num_experts=64,
            num_experts_per_token=6,
            num_shared_experts=2,
            expert_intermediate_size=1408,
        ),
        moe_layer_start=1,
    )
)

# DeepSeek-V2-Lite (16B-class MLA + MoE; HF deepseek_v2 arch): full-rank
# q (q_lora_rank null in the HF config), compressed kv. Reference HF
# config fields mirrored 1:1.
DEEPSEEK_V2_LITE = _register(
    ModelConfig(
        name="deepseek-v2-lite",
        vocab_size=102400,
        hidden_size=2048,
        intermediate_size=10944,
        num_layers=27,
        num_heads=16,
        num_kv_heads=16,
        head_dim=192,               # qk_nope (128) + qk_rope (64)
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        # 160k window via YaRN (factor 40 over the 4k native window),
        # per the HF config's rope_scaling block.
        max_position=163840,
        rope_scaling=RopeScalingConfig(
            rope_type="yarn",
            factor=40.0,
            original_max_position=4096,
            beta_fast=32.0,
            beta_slow=1.0,
            mscale=0.707,
            mscale_all_dim=0.707,
        ),
        moe=MoEConfig(
            num_experts=64,
            num_experts_per_token=6,
            num_shared_experts=2,
            expert_intermediate_size=1408,
        ),
        moe_layer_start=1,
        mla=MLAConfig(
            q_lora_rank=0,
            kv_lora_rank=512,
            qk_nope_head_dim=128,
            qk_rope_head_dim=64,
            v_head_dim=128,
            latent_cache=True,
        ),
    )
)

# DeepSeek-V3 (671B total / 37B active; BASELINE config 3): MLA with
# low-rank q, 256 routed experts top-8 + 1 shared, SIGMOID router scoring
# with the noaux_tc selection bias and group-limited top-k (n_group 8,
# topk_group 4), norm_topk_prob and routed scaling 2.5 per the HF config.
DEEPSEEK_V3 = _register(
    ModelConfig(
        name="deepseek-v3",
        vocab_size=129280,
        hidden_size=7168,
        intermediate_size=18432,
        num_layers=61,
        num_heads=128,
        num_kv_heads=128,
        head_dim=192,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        # 160k via YaRN (factor 40, mscale 1.0 both) per the HF config.
        max_position=163840,
        rope_scaling=RopeScalingConfig(
            rope_type="yarn",
            factor=40.0,
            original_max_position=4096,
            beta_fast=32.0,
            beta_slow=1.0,
            mscale=1.0,
            mscale_all_dim=1.0,
        ),
        moe=MoEConfig(
            num_experts=256,
            num_experts_per_token=8,
            num_shared_experts=1,
            expert_intermediate_size=2048,
            norm_topk_prob=True,
            routed_scaling_factor=2.5,
            scoring_func="sigmoid",
            n_group=8,
            topk_group=4,
        ),
        moe_layer_start=3,
        mla=MLAConfig(
            q_lora_rank=1536,
            kv_lora_rank=512,
            qk_nope_head_dim=128,
            qk_rope_head_dim=64,
            v_head_dim=128,
            latent_cache=True,
        ),
    )
)

# GLM-4.7-Flash (zai-org; HF glm4_moe_lite; 29.9B parameters, 3.3B of them
# a token's outside the embedding and the head): MLA in every layer with a
# low-rank query, heads of 192 + 64 query dims and 256 value dims (the first
# shapes here whose value is as wide as its query: nothing is padded), one
# dense layer, then 46 layers of 64 routed experts, top-4 by sigmoid score
# with the noaux_tc selection bias (one group: no group limit), weights
# renormalised and scaled by 1.8, and one shared expert. The router names its
# own width (``router_experts``), so the expert layers are ``llama._moe_share``
# with every expert held: dropless, work in proportion to the assignments.
# ``num_nextn_predict_layers: 1`` is a drafting layer outside the 47 and is
# not part of this model (ROADMAP M6).
GLM_4_7_FLASH = _register(
    ModelConfig(
        name="glm-4.7-flash",
        vocab_size=154880,
        hidden_size=2048,
        intermediate_size=10240,
        num_layers=47,
        num_heads=20,
        num_kv_heads=20,
        head_dim=256,               # qk_nope (192) + qk_rope (64)
        rope_theta=1000000.0,
        rms_norm_eps=1e-5,
        max_position=202752,
        moe=MoEConfig(
            num_experts=64,
            num_experts_per_token=4,
            num_shared_experts=1,
            expert_intermediate_size=1536,
            norm_topk_prob=True,
            routed_scaling_factor=1.8,
            scoring_func="sigmoid",
            router_experts=64,
        ),
        moe_layer_start=1,
        mla=MLAConfig(
            q_lora_rank=768,
            kv_lora_rank=512,
            qk_nope_head_dim=192,
            qk_rope_head_dim=64,
            v_head_dim=256,
            latent_cache=True,
        ),
    )
)

# Solar-Open2-250B (upstage; HF solar_open2): 48 layers in periods of four,
# layer i softmax GQA (64 query / 8 kv heads of 128, NO rotary embedding, a
# sigmoid output gate) where i % 4 == 0 and delta-rule linear attention
# (64 heads, key and value dims 128, conv 4, negative eigenvalues) otherwise;
# every layer 320 routed experts of width 1280, top-8 by sigmoid score with
# a selection bias, one shared expert. The low-rank gate width (the head
# dim) is the KDA publication's choice; the config leaves it out.
SOLAR_OPEN2_250B = _register(
    ModelConfig(
        name="solar-open2-250b",
        vocab_size=196608,
        hidden_size=4096,
        intermediate_size=10240,
        num_layers=48,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        rope_theta=10000.0,
        rms_norm_eps=1e-5,
        max_position=1048576,
        moe=MoEConfig(
            num_experts=320,
            num_experts_per_token=8,
            num_shared_experts=1,
            expert_intermediate_size=1280,
            norm_topk_prob=True,
            routed_scaling_factor=1.0,
            scoring_func="sigmoid",
            router_experts=320,
            first_expert=0,
        ),
        moe_layer_start=0,
        mixer_period=("attn", "linear", "linear", "linear"),
        linear_attn=LinearAttnConfig(
            num_heads=64, key_head_dim=128, value_head_dim=128,
            conv_kernel=4, gate_rank=128, neg_eigval=True,
        ),
        attn_output_gate=True,
        use_rope=False,
    )
)

# One period of the same pattern at toy widths (CPU tests): 8 experts of
# which 4 are held here, so the share and the router differ.
TINY_HYBRID = _register(
    ModelConfig(
        name="tiny-hybrid",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=4,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        rope_theta=10000.0,
        max_position=4096,
        moe=MoEConfig(
            num_experts=4,
            num_experts_per_token=2,
            num_shared_experts=1,
            expert_intermediate_size=32,
            norm_topk_prob=True,
            scoring_func="sigmoid",
            router_experts=8,
            first_expert=0,
        ),
        moe_layer_start=0,
        mixer_period=("attn", "linear", "linear", "linear"),
        linear_attn=LinearAttnConfig(
            num_heads=4, key_head_dim=16, value_head_dim=16,
            conv_kernel=4, gate_rank=16, neg_eigval=True,
        ),
        attn_output_gate=True,
        use_rope=False,
    )
)

# Olmo-Hybrid-7B (allenai; HF olmo_hybrid): 32 dense layers in periods of
# three gated delta-rule layers (FLA's Gated DeltaNet: 30 heads, keys 96 and
# values 192 a head, one decay a head, full-rank gates, conv 4, negative
# eigenvalues) and one full-attention layer LAST (30 heads and 30 kv heads
# of 128, RMSNorm over the whole q and k, no rotary embedding), in the Olmo2
# block order: the norm follows the sublayer.
OLMO_HYBRID_7B = _register(
    ModelConfig(
        name="olmo-hybrid-7b",
        vocab_size=100352,
        hidden_size=3840,
        intermediate_size=11008,
        num_layers=32,
        num_heads=30,
        num_kv_heads=30,
        rms_norm_eps=1e-6,
        qk_norm=True,
        qk_norm_whole=True,
        max_position=65536,
        mixer_period=("linear", "linear", "linear", "attn"),
        linear_attn=LinearAttnConfig(
            num_heads=30, key_head_dim=96, value_head_dim=192,
            conv_kernel=4, gate_rank=0, neg_eigval=True,
            decay="head", gates="full",
        ),
        use_rope=False,
        post_norm=True,
    )
)

# Two periods of the same pattern at toy widths (CPU tests), key and value
# dims unequal as in the model.
TINY_OLMO_HYBRID = _register(
    ModelConfig(
        name="tiny-olmo-hybrid",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=8,
        num_heads=4,
        num_kv_heads=4,
        rms_norm_eps=1e-6,
        qk_norm=True,
        qk_norm_whole=True,
        max_position=4096,
        mixer_period=("linear", "linear", "linear", "attn"),
        linear_attn=LinearAttnConfig(
            num_heads=4, key_head_dim=12, value_head_dim=24,
            conv_kernel=4, gate_rank=0, neg_eigval=True,
            decay="head", gates="full",
        ),
        use_rope=False,
        post_norm=True,
    )
)

# AI21-Jamba2-3B (ai21labs; HF jamba; 3.03B parameters): 28 pre-norm dense
# layers in periods of 14, layer i attention where i % 14 == 7 (20 query
# heads over ONE kv head of 128, no rotary embedding) and a Mamba-1 selective
# scan otherwise (d_inner 5120, a state of 16 a channel, conv 4, dt through a
# rank of 160, Jamba's RMSNorm on dt, B and C); ``num_experts`` is 1, so every
# MLP is the dense SwiGLU of 8192; the head is the embedding.
JAMBA2_3B = _register(
    ModelConfig(
        name="jamba2-3b",
        vocab_size=65536,
        hidden_size=2560,
        intermediate_size=8192,
        num_layers=28,
        num_heads=20,
        num_kv_heads=1,
        rms_norm_eps=1e-6,
        tie_embeddings=True,
        max_position=262144,
        mixer_period=("mamba",) * 7 + ("attn",) + ("mamba",) * 6,
        mamba=MambaConfig(
            d_inner=5120, d_state=16, d_conv=4, dt_rank=160, conv_bias=True),
        use_rope=False,
    )
)

# The same model with a head of its own: what the benchmark serves, whose
# harness draws the embedding (bfloat16) and the head (int8) apart and whose
# reference reads the head (``benchmarks/configs/jamba2-3b-int8.json``,
# ``assumed``). The same matmul, 0.17 GB read a pass where the tied bfloat16
# rows would be 0.34.
JAMBA2_3B_UNTIED = _register(
    replace(JAMBA2_3B, name="jamba2-3b-untied", tie_embeddings=False))

# Two shortened periods of the same pattern at toy widths (CPU tests): Mamba
# layers on both sides of the one multi-query attention layer.
TINY_JAMBA = _register(
    ModelConfig(
        name="tiny-jamba",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=8,
        num_heads=4,
        num_kv_heads=1,
        rms_norm_eps=1e-6,
        tie_embeddings=True,
        max_position=4096,
        mixer_period=("mamba", "mamba", "attn", "mamba"),
        mamba=MambaConfig(
            d_inner=128, d_state=16, d_conv=4, dt_rank=8, conv_bias=True),
        use_rope=False,
    )
)

TINY_MLA = _register(
    ModelConfig(
        name="tiny-mla",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=4,
        head_dim=24,                # 16 nope + 8 rope
        rope_theta=10000.0,
        max_position=2048,
        mla=MLAConfig(
            q_lora_rank=32,
            kv_lora_rank=32,
            qk_nope_head_dim=16,
            qk_rope_head_dim=8,
            v_head_dim=16,
        ),
    )
)

TINY_MOE = _register(
    ModelConfig(
        name="tiny-moe",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        rope_theta=10000.0,
        max_position=2048,
        moe=MoEConfig(
            num_experts=4,
            num_experts_per_token=2,
            num_shared_experts=1,
            expert_intermediate_size=64,
        ),
        moe_layer_start=1,
    )
)

# GLM-4.7-Flash's shape at toy widths (CPU tests): latent pages under heads
# whose value is as wide as their query, one dense layer, then expert layers
# behind a sigmoid router that names its own width, all experts held.
TINY_GLM_FLASH = _register(
    ModelConfig(
        name="tiny-glm-flash",
        vocab_size=512,
        hidden_size=64,
        intermediate_size=128,
        num_layers=3,
        num_heads=4,
        num_kv_heads=4,
        head_dim=24,                # 16 nope + 8 rope
        rope_theta=10000.0,
        rms_norm_eps=1e-5,
        max_position=4096,
        moe=MoEConfig(
            num_experts=8,
            num_experts_per_token=2,
            num_shared_experts=1,
            expert_intermediate_size=32,
            norm_topk_prob=True,
            routed_scaling_factor=1.8,
            scoring_func="sigmoid",
            router_experts=8,
        ),
        moe_layer_start=1,
        mla=MLAConfig(
            q_lora_rank=32,
            kv_lora_rank=32,
            qk_nope_head_dim=16,
            qk_rope_head_dim=8,
            v_head_dim=24,
            latent_cache=True,
        ),
    )
)


def get_config_preset(name: str) -> ModelConfig:
    if name in PRESETS:
        return PRESETS[name]
    raise KeyError(f"unknown model preset '{name}' (have: {sorted(PRESETS)})")


def config_from_hf(path: str, name: str = "") -> ModelConfig:
    """Derive a ModelConfig from an HF checkpoint dir's ``config.json``
    (model_type ``llama`` / ``mistral`` (non-sliding-window releases) /
    ``qwen2`` / ``deepseek`` / ``deepseek_v2`` / ``deepseek_v3`` — the
    dense, MoE, and MLA families this engine serves), so ANY such HF
    checkpoint directory is servable without a hand-written preset.
    The reference needs no model configs at all —
    its "model" is a remote API (reference pkg/llms/openai.go:69); here
    the checkpoint's own metadata is the source of truth. ``path`` may
    be the dir or the json file."""
    import json
    import os

    cfg_path = (
        os.path.join(path, "config.json") if os.path.isdir(path) else path
    )
    with open(cfg_path, encoding="utf-8") as f:
        hf = json.load(f)
    mt = hf.get("model_type", "llama")
    if mt not in ("llama", "mistral", "qwen2", "qwen3", "qwen3_moe",
                  "deepseek", "deepseek_v2", "deepseek_v3", "solar_open2",
                  "olmo_hybrid", "glm4_moe_lite", "jamba"):
        raise ValueError(
            f"config_from_hf supports model_type llama/mistral/qwen2/"
            f"qwen3/qwen3_moe/deepseek/deepseek_v2/deepseek_v3/solar_open2/"
            f"olmo_hybrid/glm4_moe_lite/jamba, got {mt!r}"
        )
    name = name or os.path.basename(os.path.normpath(
        path if os.path.isdir(path) else os.path.dirname(cfg_path)
    )) or mt
    if mt == "solar_open2":
        return _solar_open2_from_hf(hf, name)
    if mt == "olmo_hybrid":
        return _olmo_hybrid_from_hf(hf, name)
    if mt == "glm4_moe_lite":
        return _glm4_moe_lite_from_hf(hf, name)
    if mt == "jamba":
        return _jamba_from_hf(hf, name)
    # Sliding-window attention is not implemented; a config that would
    # ACTIVELY use it must be rejected loudly, never silently served
    # with full attention. Mistral (llama-shaped otherwise: same weight
    # names, GQA, silu, RMSNorm): active when sliding_window is non-null
    # and below the position window — only v0.1-class checkpoints;
    # v0.2+/Nemo/Small ship sliding_window: null. Qwen2 carries the same
    # fields but gates them with use_sliding_window (shipped Qwen2.5
    # releases set it false).
    sw = hf.get("sliding_window")
    sw_active = sw is not None and int(sw) < int(
        hf.get("max_position_embeddings", 8192)
    )
    if mt in ("qwen2", "qwen3", "qwen3_moe"):
        sw_active = sw_active and bool(hf.get("use_sliding_window", False))
    if sw_active and not mt.startswith("deepseek"):
        raise ValueError(
            f"checkpoint uses ACTIVE sliding-window attention "
            f"(sliding_window={sw}); this engine serves full paged "
            f"attention only — use a release with the window disabled "
            f"(sliding_window: null)"
        )
    moe = None
    mla = None
    moe_layer_start = 0
    if mt == "qwen3_moe":
        # Qwen3-MoE uses the deepseek WEIGHT naming (mlp.gate router,
        # mlp.experts.N.*_proj) with its own CONFIG key names and
        # softmax-then-topk routing, no shared experts. Interleaved
        # dense layers (decoder_sparse_step != 1 or mlp_only_layers)
        # are not the contiguous dense-prefix layout this engine's
        # stacked tree supports — reject rather than mis-route.
        if hf.get("mlp_only_layers"):
            raise ValueError(
                "qwen3_moe mlp_only_layers interleaving is not supported"
            )
        if int(hf.get("decoder_sparse_step", 1) or 1) != 1:
            raise ValueError(
                "only decoder_sparse_step=1 (every layer MoE) is supported"
            )
        moe = MoEConfig(
            num_experts=int(hf["num_experts"]),
            num_experts_per_token=int(hf["num_experts_per_tok"]),
            num_shared_experts=0,
            expert_intermediate_size=int(hf["moe_intermediate_size"]),
            norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
            routed_scaling_factor=1.0,
            scoring_func="softmax",
            n_group=1,
            topk_group=1,
        )
    if mt.startswith("deepseek"):
        if int(hf.get("moe_layer_freq", 1)) != 1:
            raise ValueError(
                "only moe_layer_freq=1 (contiguous MoE stack after the "
                "dense prefix) is supported"
            )
        if hf.get("n_routed_experts"):
            scoring = hf.get("scoring_func", "softmax")
            if scoring not in ("softmax", "sigmoid"):
                # Reject rather than silently routing with softmax
                # semantics (models.llama falls back to softmax for
                # unknown scoring functions).
                raise ValueError(
                    f"unsupported router scoring_func {scoring!r}"
                )
            moe = MoEConfig(
                num_experts=int(hf["n_routed_experts"]),
                num_experts_per_token=int(hf["num_experts_per_tok"]),
                num_shared_experts=int(hf.get("n_shared_experts", 0) or 0),
                expert_intermediate_size=int(
                    hf.get("moe_intermediate_size", 0) or 0
                ),
                norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
                routed_scaling_factor=float(
                    hf.get("routed_scaling_factor", 1.0)
                ),
                scoring_func=scoring,
                n_group=int(hf.get("n_group", 1) or 1),
                topk_group=int(hf.get("topk_group", 1) or 1),
            )
            moe_layer_start = int(hf.get("first_k_dense_replace", 0))
        if mt in ("deepseek_v2", "deepseek_v3"):
            mla = MLAConfig(
                q_lora_rank=int(hf.get("q_lora_rank") or 0),
                kv_lora_rank=int(hf["kv_lora_rank"]),
                qk_nope_head_dim=int(hf["qk_nope_head_dim"]),
                qk_rope_head_dim=int(hf["qk_rope_head_dim"]),
                v_head_dim=int(hf["v_head_dim"]),
                # Serve V2/V3 with the compressed latent pages — the
                # whole point of MLA (engine latent-cache path).
                latent_cache=True,
            )
    rs = None
    hf_rs = hf.get("rope_scaling") or None
    if hf_rs:
        rt = hf_rs.get("rope_type") or hf_rs.get("type")
        if rt == "llama3":
            rs = RopeScalingConfig(
                rope_type="llama3",
                factor=float(hf_rs["factor"]),
                original_max_position=int(
                    hf_rs["original_max_position_embeddings"]
                ),
                low_freq_factor=float(hf_rs.get("low_freq_factor", 1.0)),
                high_freq_factor=float(hf_rs.get("high_freq_factor", 4.0)),
            )
        elif rt == "yarn":
            rs = RopeScalingConfig(
                rope_type="yarn",
                factor=float(hf_rs["factor"]),
                original_max_position=int(
                    hf_rs["original_max_position_embeddings"]
                ),
                beta_fast=float(hf_rs.get("beta_fast", 32.0)),
                beta_slow=float(hf_rs.get("beta_slow", 1.0)),
                mscale=float(hf_rs.get("mscale", 1.0)),
                mscale_all_dim=float(hf_rs.get("mscale_all_dim", 0.0)),
            )
        else:
            raise ValueError(f"unsupported rope_scaling type {rt!r}")
    heads = int(hf["num_attention_heads"])
    return ModelConfig(
        name=name,
        vocab_size=int(hf["vocab_size"]),
        hidden_size=int(hf["hidden_size"]),
        intermediate_size=int(hf["intermediate_size"]),
        num_layers=int(hf["num_hidden_layers"]),
        num_heads=heads,
        # MLA has no GQA: the latent is the compression.
        num_kv_heads=heads if mla else int(
            hf.get("num_key_value_heads", heads)
        ),
        head_dim=mla.qk_head_dim if mla else int(hf.get("head_dim") or 0),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        # Qwen2 checkpoints carry q/k/v biases without an explicit flag;
        # Qwen3 dropped the biases for per-head q/k RMSNorm instead.
        attn_bias=(mt == "qwen2") or bool(hf.get("attention_bias", False)),
        qk_norm=mt in ("qwen3", "qwen3_moe"),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        max_position=int(hf.get("max_position_embeddings", 8192)),
        moe=moe,
        moe_layer_start=moe_layer_start,
        mla=mla,
        rope_scaling=rs,
    )


def _solar_open2_from_hf(hf: dict, name: str) -> ModelConfig:
    """``model_type: solar_open2``: softmax GQA layers (no rotary embedding,
    a sigmoid output gate) every ``gqa_interval + 1`` layers, delta-rule
    linear attention between them, DeepSeek-V3-style experts in every layer.
    What the config leaves out follows the KDA publication: low-rank decay
    and output gates as wide as a head, sigmoid scores with a selection
    bias. ``experts_held`` / ``first_expert_held`` (this engine's own keys,
    written by ``hf_config_dict``) say which share of the experts is here."""
    period = int(hf.get("gqa_interval", 3)) + 1
    layers = int(hf["num_hidden_layers"])
    gqa = [i for i in hf.get("gqa_layers", range(0, layers, period))
           if i < layers]
    if gqa != list(range(0, layers, period)):
        raise ValueError(
            f"solar_open2: gqa_layers {gqa} are not every {period}th layer "
            f"from 0: only a periodic pattern is supported"
        )
    if int(hf.get("first_k_dense_replace", 0)) % period:
        raise ValueError("solar_open2: first_k_dense_replace splits a period")
    if hf.get("kda_use_full_proj", False):
        raise ValueError("solar_open2: kda_use_full_proj=true is not supported")
    if float(hf.get("partial_rotary_factor", 1)) != 1 and hf.get("use_rope"):
        raise ValueError("solar_open2: partial rotary embedding is not supported")
    la = hf["linear_attn_config"]
    la_heads = int(la["num_heads"])
    if la.get("num_kv_heads") not in (None, la_heads):
        raise ValueError("solar_open2: grouped linear-attention heads")
    la_dim = int(la["head_dim"])
    routed = int(hf["n_routed_experts"])
    return ModelConfig(
        name=name,
        vocab_size=int(hf["vocab_size"]),
        hidden_size=int(hf["hidden_size"]),
        intermediate_size=int(hf["intermediate_size"]),
        num_layers=layers,
        num_heads=int(hf["num_attention_heads"]),
        num_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf.get("head_dim") or 0),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        max_position=int(hf.get("max_position_embeddings", 8192)),
        moe=MoEConfig(
            num_experts=int(hf.get("experts_held", routed)),
            num_experts_per_token=int(hf["num_experts_per_tok"]),
            num_shared_experts=int(hf.get("n_shared_experts", 0) or 0),
            expert_intermediate_size=int(hf["moe_intermediate_size"]),
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            scoring_func="sigmoid",
            router_experts=routed,
            first_expert=int(hf.get("first_expert_held", 0)),
        ),
        moe_layer_start=int(hf.get("first_k_dense_replace", 0)),
        mixer_period=("attn",) + ("linear",) * (period - 1),
        linear_attn=LinearAttnConfig(
            num_heads=la_heads, key_head_dim=la_dim, value_head_dim=la_dim,
            conv_kernel=int(la.get("short_conv_kernel_size", 4)),
            gate_rank=la_dim,
            neg_eigval=bool(hf.get("kda_allow_neg_eigval", False)),
        ),
        attn_output_gate=bool(hf.get("use_gqa_gate", False)),
        use_rope=bool(hf.get("use_rope", True)),
    )


def _solar_open2_dict(cfg: ModelConfig) -> dict:
    """The inverse of ``_solar_open2_from_hf``."""
    la, m = cfg.linear_attn, cfg.moe
    period = len(cfg.mixer_period)
    if (cfg.mixer_period != ("attn",) + ("linear",) * (period - 1)
            or m is None or m.scoring_func != "sigmoid" or cfg.mla
            or la.key_head_dim != la.value_head_dim
            or la.gate_rank != la.key_head_dim
            or (la.decay, la.gates) != ("channel", "low_rank")):
        raise ValueError(
            "hf_config_dict: this layer pattern is not expressible as "
            "model_type solar_open2"
        )
    hf = {
        "model_type": "solar_open2",
        "architectures": ["SolarOpen2ForCausalLM"],
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "moe_intermediate_size": m.expert_intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim_,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "max_position_embeddings": cfg.max_position,
        "partial_rotary_factor": 1,
        "use_rope": cfg.use_rope,
        "use_gqa_gate": cfg.attn_output_gate,
        "gqa_interval": period - 1,
        "gqa_layers": list(range(0, cfg.num_layers, period)),
        "linear_attn_config": {
            "short_conv_kernel_size": la.conv_kernel,
            "head_dim": la.key_head_dim,
            "num_heads": la.num_heads,
            "num_kv_heads": None,
        },
        "kda_use_full_proj": False,
        "kda_allow_neg_eigval": la.neg_eigval,
        "first_k_dense_replace": cfg.moe_layer_start,
        "n_routed_experts": m.router_width,
        "n_shared_experts": m.num_shared_experts,
        "num_experts_per_tok": m.num_experts_per_token,
        "norm_topk_prob": m.norm_topk_prob,
        "routed_scaling_factor": m.routed_scaling_factor,
    }
    if m.num_experts != m.router_width or m.first_expert:
        hf["experts_held"] = m.num_experts
        hf["first_expert_held"] = m.first_expert
    return hf


def _glm4_moe_lite_from_hf(hf: dict, name: str) -> ModelConfig:
    """``model_type: glm4_moe_lite`` (GLM-4.7-Flash): DeepSeek-V3's layer
    under other numbers: MLA with a low-rank query in every layer,
    ``first_k_dense_replace`` dense layers, then routed experts chosen by
    sigmoid score plus a selection bias (``topk_method: noaux_tc``; the
    config has no ``scoring_func`` key, the method implies it) beside shared
    experts. The router names its own width, so the expert layers run as a
    share with every expert held (``experts_held`` / ``first_expert_held``,
    this engine's own keys, name a smaller one). The drafting layer
    (``num_nextn_predict_layers``) is not part of the served model."""
    if hf.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError(
            f"glm4_moe_lite: topk_method {hf['topk_method']!r} is not "
            "supported (noaux_tc: sigmoid scores and a selection bias)")
    if int(hf.get("n_group", 1) or 1) != 1 or int(
            hf.get("topk_group", 1) or 1) != 1:
        raise ValueError(
            "glm4_moe_lite: group-limited routing (n_group > 1) is not "
            "supported inside an expert share")
    if float(hf.get("partial_rotary_factor", 1)) != 1:
        raise ValueError("glm4_moe_lite: partial rotary embedding is not supported")
    if hf.get("rope_scaling"):
        raise ValueError("glm4_moe_lite: rope_scaling is not supported")
    heads = int(hf["num_attention_heads"])
    if int(hf.get("num_key_value_heads", heads)) != heads:
        raise ValueError("glm4_moe_lite: MLA has no grouped kv heads")
    mla = MLAConfig(
        q_lora_rank=int(hf.get("q_lora_rank") or 0),
        kv_lora_rank=int(hf["kv_lora_rank"]),
        qk_nope_head_dim=int(hf["qk_nope_head_dim"]),
        qk_rope_head_dim=int(hf["qk_rope_head_dim"]),
        v_head_dim=int(hf["v_head_dim"]),
        latent_cache=True,
    )
    routed = int(hf["n_routed_experts"])
    return ModelConfig(
        name=name,
        vocab_size=int(hf["vocab_size"]),
        hidden_size=int(hf["hidden_size"]),
        intermediate_size=int(hf["intermediate_size"]),
        num_layers=int(hf["num_hidden_layers"]),
        num_heads=heads,
        num_kv_heads=heads,
        head_dim=mla.qk_head_dim,
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-5)),
        attn_bias=bool(hf.get("attention_bias", False)),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        max_position=int(hf.get("max_position_embeddings", 8192)),
        moe=MoEConfig(
            num_experts=int(hf.get("experts_held", routed)),
            num_experts_per_token=int(hf["num_experts_per_tok"]),
            num_shared_experts=int(hf.get("n_shared_experts", 0) or 0),
            expert_intermediate_size=int(hf["moe_intermediate_size"]),
            norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            scoring_func="sigmoid",
            router_experts=routed,
            first_expert=int(hf.get("first_expert_held", 0)),
        ),
        moe_layer_start=int(hf.get("first_k_dense_replace", 0)),
        mla=mla,
    )


def _glm4_moe_lite_dict(cfg: ModelConfig) -> dict:
    """The inverse of ``_glm4_moe_lite_from_hf``."""
    a, m = cfg.mla, cfg.moe
    if (cfg.rope_scaling or cfg.qk_norm or m.scoring_func != "sigmoid"
            or m.n_group > 1 or not a.latent_cache):
        raise ValueError(
            "hf_config_dict: this model is not expressible as model_type "
            "glm4_moe_lite"
        )
    hf = {
        "model_type": "glm4_moe_lite",
        "architectures": ["Glm4MoeLiteForCausalLM"],
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "moe_intermediate_size": m.expert_intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "hidden_act": "silu",
        "attention_bias": cfg.attn_bias,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": None,
        "partial_rotary_factor": 1,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "max_position_embeddings": cfg.max_position,
        "q_lora_rank": a.q_lora_rank or None,
        "kv_lora_rank": a.kv_lora_rank,
        "qk_nope_head_dim": a.qk_nope_head_dim,
        "qk_rope_head_dim": a.qk_rope_head_dim,
        "v_head_dim": a.v_head_dim,
        "topk_method": "noaux_tc",
        "n_group": 1,
        "topk_group": 1,
        "first_k_dense_replace": cfg.moe_layer_start,
        "n_routed_experts": m.router_width,
        "n_shared_experts": m.num_shared_experts,
        "num_experts_per_tok": m.num_experts_per_token,
        "norm_topk_prob": m.norm_topk_prob,
        "routed_scaling_factor": m.routed_scaling_factor,
    }
    if m.num_experts != m.router_width or m.first_expert:
        hf["experts_held"] = m.num_experts
        hf["first_expert_held"] = m.first_expert
    return hf


def _jamba_from_hf(hf: dict, name: str) -> ModelConfig:
    """``model_type: jamba``: pre-norm layers, layer ``i`` attention where
    ``i % attn_layer_period == attn_layer_offset`` (the model type's own
    rule in transformers; grouped-query, no rotary embedding) and a Mamba-1
    mixer otherwise, a dense SwiGLU MLP in every layer. A config with
    ``num_experts`` > 1 puts experts in every ``expert_layer_period``-th
    MLP, which this engine does not run for this model type: refused by
    name (the ``expert_layer_*`` keys are inert at one expert)."""
    experts = int(hf.get("num_experts", 1) or 1)
    if experts > 1:
        raise ValueError(
            f"jamba: num_experts={experts} is not supported: only the dense "
            "models of the family (num_experts 1, every MLP a SwiGLU)")
    if hf.get("sliding_window") is not None:
        raise ValueError("jamba: sliding_window attention is not supported")
    if hf.get("mamba_proj_bias", False):
        raise ValueError("jamba: mamba_proj_bias=true is not supported")
    layers = int(hf["num_hidden_layers"])
    period = int(hf.get("attn_layer_period", 8))
    offset = int(hf.get("attn_layer_offset", 4))
    if not 0 <= offset < period or layers % period:
        raise ValueError(
            f"jamba: {layers} layers are not whole periods of "
            f"attn_layer_period={period} (offset {offset})")
    d = int(hf["hidden_size"])
    rank = hf.get("mamba_dt_rank", "auto")
    return ModelConfig(
        name=name,
        vocab_size=int(hf["vocab_size"]),
        hidden_size=d,
        intermediate_size=int(hf["intermediate_size"]),
        num_layers=layers,
        num_heads=int(hf["num_attention_heads"]),
        num_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf.get("head_dim") or 0),
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        max_position=int(hf.get("max_position_embeddings", 262144)),
        mixer_period=tuple(
            "attn" if i == offset else "mamba" for i in range(period)),
        mamba=MambaConfig(
            d_inner=int(hf.get("mamba_expand", 2)) * d,
            d_state=int(hf.get("mamba_d_state", 16)),
            d_conv=int(hf.get("mamba_d_conv", 4)),
            dt_rank=-(-d // 16) if rank == "auto" else int(rank),
            conv_bias=bool(hf.get("mamba_conv_bias", True)),
        ),
        use_rope=False,
    )


def _jamba_dict(cfg: ModelConfig) -> dict:
    """The inverse of ``_jamba_from_hf``."""
    mc, period = cfg.mamba, cfg.mixer_period
    if (cfg.moe or cfg.mla or cfg.linear_attn or cfg.use_rope or cfg.qk_norm
            or cfg.attn_bias or cfg.attn_output_gate or cfg.post_norm
            or period.count("attn") != 1 or mc.d_inner % cfg.hidden_size):
        raise ValueError(
            "hf_config_dict: this model is not expressible as model_type "
            "jamba"
        )
    hf = {
        "model_type": "jamba",
        "architectures": ["JambaForCausalLM"],
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "hidden_act": "silu",
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "max_position_embeddings": cfg.max_position,
        "sliding_window": None,
        "num_logits_to_keep": 1,
        "attn_layer_period": len(period),
        "attn_layer_offset": period.index("attn"),
        "num_experts": 1,
        "num_experts_per_tok": 1,
        "expert_layer_period": 2,
        "expert_layer_offset": 1,
        "use_mamba_kernels": True,
        "mamba_expand": mc.d_inner // cfg.hidden_size,
        "mamba_d_state": mc.d_state,
        "mamba_d_conv": mc.d_conv,
        "mamba_dt_rank": mc.dt_rank,
        "mamba_conv_bias": mc.conv_bias,
        "mamba_proj_bias": False,
    }
    if cfg.head_dim:
        hf["head_dim"] = cfg.head_dim
    return hf


_OLMO_LAYER_TYPES = {"linear_attention": "linear", "full_attention": "attn"}


def _olmo_hybrid_from_hf(hf: dict, name: str) -> ModelConfig:
    """``model_type: olmo_hybrid``: dense layers whose ``layer_types`` repeat
    a period of gated delta-rule layers and full-attention layers (the
    ``linear_*`` keys are FLA's Gated DeltaNet: one decay a head, full-rank
    gates). What the config has no key for follows the family (Olmo2 /
    Olmo3): the norm after each sublayer, an RMSNorm over the whole q and
    k; ``rope_parameters.rope_theta`` null means no rotary embedding."""
    layers = int(hf["num_hidden_layers"])
    types = list(hf.get("layer_types") or ["full_attention"] * layers)
    unknown = sorted(set(types) - set(_OLMO_LAYER_TYPES))
    if unknown or len(types) != layers:
        raise ValueError(
            f"olmo_hybrid: layer_types {unknown or len(types)} not supported "
            f"(have {sorted(_OLMO_LAYER_TYPES)}, one for each layer)"
        )
    period = next(
        p for p in range(1, layers + 1)
        if layers % p == 0 and types == types[:p] * (layers // p)
    )
    heads = int(hf["linear_num_value_heads"])
    if int(hf.get("linear_num_key_heads", heads)) != heads:
        raise ValueError("olmo_hybrid: grouped linear-attention heads")
    theta = (hf.get("rope_parameters") or {}).get(
        "rope_theta", hf.get("rope_theta"))
    return ModelConfig(
        name=name,
        vocab_size=int(hf["vocab_size"]),
        hidden_size=int(hf["hidden_size"]),
        intermediate_size=int(hf["intermediate_size"]),
        num_layers=layers,
        num_heads=int(hf["num_attention_heads"]),
        num_kv_heads=int(hf["num_key_value_heads"]),
        head_dim=int(hf.get("head_dim") or 0),
        rope_theta=float(theta) if theta is not None else ModelConfig.rope_theta,
        rms_norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        attn_bias=bool(hf.get("attention_bias", False)),
        qk_norm=True,
        qk_norm_whole=True,
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        max_position=int(hf.get("max_position_embeddings", 8192)),
        mixer_period=tuple(_OLMO_LAYER_TYPES[t] for t in types[:period]),
        linear_attn=LinearAttnConfig(
            num_heads=heads,
            key_head_dim=int(hf["linear_key_head_dim"]),
            value_head_dim=int(hf["linear_value_head_dim"]),
            conv_kernel=int(hf.get("linear_conv_kernel_dim", 4)),
            gate_rank=0,
            neg_eigval=bool(hf.get("linear_allow_neg_eigval", False)),
            decay="head", gates="full",
        ),
        use_rope=theta is not None,
        post_norm=True,
    )


def _olmo_hybrid_dict(cfg: ModelConfig) -> dict:
    """The inverse of ``_olmo_hybrid_from_hf``."""
    la = cfg.linear_attn
    if (la is None or cfg.moe or cfg.mla
            or not (cfg.qk_norm and cfg.qk_norm_whole)
            or cfg.attn_output_gate or cfg.rope_scaling
            or (la.decay, la.gates) != ("head", "full")):
        raise ValueError(
            "hf_config_dict: this model is not expressible as model_type "
            "olmo_hybrid"
        )
    names = {v: k for k, v in _OLMO_LAYER_TYPES.items()}
    hf = {
        "model_type": "olmo_hybrid",
        "architectures": ["OlmoHybridForCausalLM"],
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "hidden_act": "silu",
        "max_position_embeddings": cfg.max_position,
        "attention_bias": cfg.attn_bias,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "layer_types": [names[cfg.mixer_of(i)] for i in range(cfg.num_layers)],
        "linear_num_key_heads": la.num_heads,
        "linear_num_value_heads": la.num_heads,
        "linear_key_head_dim": la.key_head_dim,
        "linear_value_head_dim": la.value_head_dim,
        "linear_conv_kernel_dim": la.conv_kernel,
        "linear_allow_neg_eigval": la.neg_eigval,
        "rope_parameters": {
            "rope_theta": cfg.rope_theta if cfg.use_rope else None},
    }
    if cfg.head_dim:
        hf["head_dim"] = cfg.head_dim
    return hf


def resolve_model(
    model_name: str, checkpoint: str = ""
) -> tuple[str, Optional[ModelConfig]]:
    """Resolve a --model-name flag to ``(name, model_cfg_or_None)``.

    A preset name passes through with ``None`` (the engine resolves the
    preset itself); ``auto`` derives the architecture from the checkpoint
    dir's ``config.json`` via ``config_from_hf``. In auto mode the
    checkpoint's own metadata is AUTHORITATIVE — even when the dir's
    basename collides with a preset name (a renamed snapshot or a
    fine-tune with different dims must serve with ITS config, not the
    preset's). One shared policy for serve-engine and
    scripts/run_real_checkpoint.py."""
    if model_name != "auto":
        return model_name, None
    if not checkpoint:
        raise ValueError("--model-name auto requires --checkpoint")
    cfg = config_from_hf(checkpoint)
    return cfg.name, cfg


def hf_config_dict(cfg: ModelConfig) -> dict:
    """``config.json`` contents for a ModelConfig — the inverse of
    ``config_from_hf`` (checkpoint export). Dense configs emit
    llama/qwen2; qk_norm configs emit qwen3 (or qwen3_moe when paired
    with a plain softmax MoE); other MoE and/or MLA configs emit the
    deepseek family (deepseek_v2/v3 when MLA is present, deepseek
    otherwise); latent attention beside an expert share emits
    glm4_moe_lite; Mamba layers emit jamba."""
    if cfg.post_norm:
        return _olmo_hybrid_dict(cfg)
    if cfg.mamba is not None:
        return _jamba_dict(cfg)
    if cfg.has_state:
        return _solar_open2_dict(cfg)
    if cfg.mla and cfg.moe and cfg.moe.router_experts:
        # latent attention beside an expert share: the router names its
        # own width, which no deepseek config can say
        return _glm4_moe_lite_dict(cfg)
    if (cfg.mixer_period or cfg.attn_output_gate or not cfg.use_rope
            or cfg.qk_norm_whole):
        raise ValueError(
            "hf_config_dict: a layer pattern, an attention output gate, "
            "NoPE attention or a whole-width q/k norm is only expressible "
            "as solar_open2 or olmo_hybrid"
        )
    qwen3_moe = (
        cfg.qk_norm and cfg.moe is not None and cfg.mla is None
        and cfg.moe.scoring_func == "softmax"
        and not cfg.moe.num_shared_experts
        and cfg.moe.routed_scaling_factor == 1.0
        and cfg.moe.n_group <= 1 and cfg.moe_layer_start == 0
    )
    if cfg.qk_norm and (cfg.moe or cfg.mla) and not qwen3_moe:
        # QK-norm is only expressible in the qwen3/qwen3_moe families; a
        # silent deepseek export would drop qk_norm and desync the
        # reloaded tree from the saved qn/kn weights.
        raise ValueError(
            "hf_config_dict cannot express qk_norm together with this "
            "moe/mla configuration"
        )
    if qwen3_moe:
        mt = "qwen3_moe"
    elif cfg.mla:
        mt = ("deepseek_v3" if cfg.moe and cfg.moe.scoring_func == "sigmoid"
              else "deepseek_v2")
    elif cfg.moe:
        mt = "deepseek"
    elif cfg.qk_norm:
        mt = "qwen3"
    else:
        mt = "qwen2" if cfg.attn_bias else "llama"
    archs = {
        "llama": "LlamaForCausalLM",
        "qwen2": "Qwen2ForCausalLM",
        "qwen3": "Qwen3ForCausalLM",
        "qwen3_moe": "Qwen3MoeForCausalLM",
        "deepseek": "DeepseekForCausalLM",
        "deepseek_v2": "DeepseekV2ForCausalLM",
        "deepseek_v3": "DeepseekV3ForCausalLM",
    }
    hf: dict = {
        "model_type": mt,
        "architectures": [archs[mt]],
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "max_position_embeddings": cfg.max_position,
        # Explicit so non-qwen2 model_types (the deepseek family) cannot
        # silently drop q/k/v biases on a roundtrip.
        "attention_bias": cfg.attn_bias,
    }
    if cfg.head_dim:
        hf["head_dim"] = cfg.head_dim
    if cfg.rope_scaling:
        rs = cfg.rope_scaling
        if rs.rope_type == "llama3":
            hf["rope_scaling"] = {
                "rope_type": "llama3",
                "factor": rs.factor,
                "original_max_position_embeddings": rs.original_max_position,
                "low_freq_factor": rs.low_freq_factor,
                "high_freq_factor": rs.high_freq_factor,
            }
        else:
            hf["rope_scaling"] = {
                "rope_type": rs.rope_type,
                "factor": rs.factor,
                "original_max_position_embeddings": rs.original_max_position,
                "beta_fast": rs.beta_fast,
                "beta_slow": rs.beta_slow,
                "mscale": rs.mscale,
                "mscale_all_dim": rs.mscale_all_dim,
            }
    if cfg.moe and qwen3_moe:
        m = cfg.moe
        hf.update({
            "num_experts": m.num_experts,
            "num_experts_per_tok": m.num_experts_per_token,
            "moe_intermediate_size": m.expert_intermediate_size,
            "norm_topk_prob": m.norm_topk_prob,
            "decoder_sparse_step": 1,
            "mlp_only_layers": [],
        })
    elif cfg.moe:
        m = cfg.moe
        hf.update({
            "n_routed_experts": m.num_experts,
            "num_experts_per_tok": m.num_experts_per_token,
            "n_shared_experts": m.num_shared_experts,
            "moe_intermediate_size": m.expert_intermediate_size,
            "first_k_dense_replace": cfg.moe_layer_start,
            "moe_layer_freq": 1,
            "norm_topk_prob": m.norm_topk_prob,
            "routed_scaling_factor": m.routed_scaling_factor,
            "scoring_func": m.scoring_func,
            "n_group": m.n_group,
            "topk_group": m.topk_group,
        })
    if cfg.mla:
        a = cfg.mla
        hf.update({
            "q_lora_rank": a.q_lora_rank or None,
            "kv_lora_rank": a.kv_lora_rank,
            "qk_nope_head_dim": a.qk_nope_head_dim,
            "qk_rope_head_dim": a.qk_rope_head_dim,
            "v_head_dim": a.v_head_dim,
        })
    return hf


def scaled_for_test(
    cfg: ModelConfig, vocab_size: int = 512, periods: int = 0
) -> ModelConfig:
    """Shrink a preset's vocab for fast CPU tests, keeping its shape ratios;
    ``periods`` also cuts the depth to that many WHOLE periods of the layer
    pattern (after any leading dense layers), never through one."""
    out = replace(cfg, vocab_size=vocab_size)
    if periods:
        depth = cfg.moe_layer_start if cfg.moe is not None else 0
        out = replace(out, num_layers=depth + periods * len(cfg.period_))
    return out
