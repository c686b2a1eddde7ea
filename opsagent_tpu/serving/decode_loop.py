"""Device-resident multi-step decode: N (decode + sample) steps per host
dispatch, as one XLA program.

Why this exists: the engine's original hot loop pulled the sampled token to
the host after EVERY decode step. A device->host transfer costs a full
round trip on top of the decode step's own compute, so per-token pulls cap
throughput at ~1/RTT regardless of model size. Scanning ``n_steps`` decode+sample iterations inside one
``jax.jit`` amortizes the dispatch AND the single [B, n_steps] token pull
over the whole block, leaving the device busy back-to-back.

Per-row early exit happens ON DEVICE: a row goes inactive when it samples
EOS or exhausts its per-dispatch token budget. Inactive rows stop writing KV
(their page state stays exactly "prompt + accepted[:-1]") and emit pad
tokens, which the host-side bookkeeping discards. Stop-string checks remain
host-side — the host walks each row's block output token by token and
truncates the page allocation back to what it accepted.

Replaces the per-token HTTPS round trip of the reference agent loop
(reference pkg/assistants/simple.go:343,515) with its tpu-native dual: the
round trip is now per-BLOCK, not per-token.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..models import llama
from ..models.config import ModelConfig
from ..ops.kernels import Kernels
from .sampler import NEG_INF, sample


def _record_attr(kind: str, attr, attr_kw: dict | None) -> None:
    """Forward one dispatch's composition to the goodput ledger's cost
    model (obs/attribution.py) — the per-dispatch wall-time/byte hook.
    Host float math only; never raises into the dispatch path."""
    if attr is None:
        return
    try:
        attr.dispatch(kind, **(attr_kw or {}))
    except Exception:  # noqa: BLE001 - attribution must not kill serving
        pass


def record_dispatch(
    kind: str, rows: int, steps: int, attr=None, attr_kw: dict | None = None
) -> None:
    """Host-side dispatch telemetry for the decode programs in this
    module. The loop bodies themselves are jitted — their Python runs only
    at trace time, so instrumentation inside them would count compiles,
    not dispatches. The engine calls this once per enqueued program:
    ``kind`` is "block" (decode_block_carry) or "single" (the fused
    one-step path);
    ``rows`` is how many lanes got a budget and ``steps`` the largest
    per-lane budget in the dispatch. ``attr``/``attr_kw`` carry the
    dispatch's roofline composition to the attribution ledger."""
    from .. import obs

    obs.DECODE_DISPATCHES.inc(kind=kind)
    if rows > 0 and steps > 0:
        obs.get_registry().histogram(
            "opsagent_decode_dispatch_rows",
            "Budgeted lanes per decode dispatch",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128),
        ).observe(rows)
    _record_attr(kind, attr, attr_kw)


def record_mixed_dispatch(
    decode_rows: int, prefill_tokens: int,
    attr=None, attr_kw: dict | None = None,
) -> None:
    """Composition telemetry for one MIXED prefill+decode dispatch
    (engine.step_mixed): how many decode lanes rode the dispatch and how
    many prefill chunk tokens piggybacked on its weight stream. These are
    the series the sessions-mixed bench stage uses to attribute the
    one-weight-stream-per-tick win (how full the step was is
    ``opsagent_step_tokens_total``, real over computed)."""
    from .. import obs

    obs.DECODE_DISPATCHES.inc(kind="mixed")
    obs.MIXED_DECODE_LANES.observe(max(0, decode_rows))
    obs.MIXED_PREFILL_TOKENS.observe(max(0, prefill_tokens))
    _record_attr("mixed", attr, attr_kw)


def record_async_dispatch(
    decode_rows: int, prefill_tokens: int, depth: int,
    attr=None, attr_kw: dict | None = None,
) -> None:
    """Telemetry for one ASYNC mixed dispatch (engine step_mixed_async /
    serving.async_runtime): the same composition series as the sync mixed
    tick — the async tick is the same batch shape, just pipelined — plus
    the in-flight-depth gauge the overlap proof reads. ``depth`` is the
    pipeline occupancy INCLUDING this dispatch. No ``measured_s`` ever
    rides here: the async dispatch is enqueue-only by design, so its wall
    time is not a step-time measurement."""
    from .. import obs

    obs.DECODE_DISPATCHES.inc(kind="mixed_async")
    obs.MIXED_DECODE_LANES.observe(max(0, decode_rows))
    obs.MIXED_PREFILL_TOKENS.observe(max(0, prefill_tokens))
    obs.ASYNC_INFLIGHT_DEPTH.set(depth)
    _record_attr("mixed_async", attr, attr_kw)


def record_async_commit(overlapped: bool, depth_after: int) -> None:
    """One committed async tick: ``overlapped`` is True when the commit's
    host work (pull, detokenize, stop-scan, streaming) ran while a newer
    dispatch was still executing on device — the condition the whole
    async runtime exists to create."""
    from .. import obs

    obs.ASYNC_COMMITS.inc()
    if overlapped:
        obs.ASYNC_OVERLAPPED_COMMITS.inc()
    obs.ASYNC_INFLIGHT_DEPTH.set(depth_after)


def record_ffwd_append(
    seq_id: int, run_len: int, attr=None, request_id: str | None = None,
) -> None:
    """One forced-token run spliced by the grammar fast-forward path:
    ``run_len`` tokens emitted straight from the constrained FSM's
    singleton masks, each of which would otherwise have cost its own
    forward pass. Counts the run, the tokens, and the skipped dispatches,
    drops a ``ffwd`` flight event, and charges the skipped dispatches to
    the attribution ledger at zero weight-stream cost (the consuming
    dispatch's q_tokens already carry the run's real KV/attention work)
    so ``opsagent_attr_dispatches_total`` and the goodput ledger stay
    honest about how many dispatches the grammar replaced."""
    from .. import obs

    obs.FFWD_RUNS.inc()
    obs.FFWD_TOKENS.inc(run_len)
    obs.FFWD_SKIPPED_DISPATCHES.inc(run_len)
    obs.flight.record(
        "ffwd", seq_id=seq_id, run_len=run_len, request_id=request_id,
    )
    if attr is not None:
        for _ in range(run_len):
            _record_attr("ffwd", attr, dict(weight_streams=0.0))


def mixed_step_carry(
    params: Any,
    cfg: ModelConfig,
    tokens: jax.Array,      # [B, S] int32 host-built ragged rows (prefill
                            # chunks; decode rows' col 0 is a placeholder
                            # when use_carry)
    use_carry: jax.Array,   # [B] bool: row's input token is carry_tok
                            # (decode lane continuing from the previous
                            # dispatch — its token never visited the host)
    carry_tok: jax.Array,   # [B] int32 previous dispatch's sampled tokens
    starts: jax.Array,      # [B] int32 write offsets
    q_lens: jax.Array,      # [B] int32 (0 = inert row)
    emits: jax.Array,       # [B] bool: row's sampled token is real output
                            # (decode lanes + prompt-finishing chunks);
                            # non-emitting rows keep their carry/FSM state
    cache: Any,             # paged KV pytree (donated by the jit wrapper)
    page_table: jax.Array,  # [B, MaxP]
    key: jax.Array,
    temps: jax.Array,       # [B] float32
    top_k: jax.Array,       # [B] int32
    top_p: jax.Array,       # [B] float32
    dtype: jnp.dtype = jnp.bfloat16,
    kernels: Kernels = Kernels(),   # who runs what (ops.kernels)
    mesh=None,
    # Device-side constrained decoding, same table layout as
    # decode_block_carry: row 0 of fsm_mask/fsm_dest is the FREE sentinel,
    # DFA state s lives at row s+1. carry_fsm rides the dispatch chain;
    # ov_fsm is the host-walked state for newly seated rows.
    fsm_mask: jax.Array | None = None,
    fsm_dest: jax.Array | None = None,
    carry_fsm: jax.Array | None = None,   # [B] int32
    ov_fsm: jax.Array | None = None,      # [B] int32
    step_tokens: int = 0,                 # llama.mixed_step's packed width
) -> tuple[jax.Array, Any, jax.Array]:
    """``llama.mixed_step`` with the sampled-token feedback DEVICE-RESIDENT:
    each decode lane's input token is spliced from ``carry_tok`` — the
    previous dispatch's output — so tick t+1 can be enqueued before tick
    t's tokens are pulled to host (the one-step-lookahead mixed pipeline,
    serving/async_runtime.py). Prefill chunk rows keep taking their tokens
    from the host arrays (prompt ids are host state by definition).

    Returns ``(toks [B], cache, fsm [B])`` where ``toks`` is BOTH the
    pull target for the commit phase and the next dispatch's carry: for
    emitting rows it is the sampled token, for everything else the spliced
    input (a don't-care the host never reads). The FSM state advances only
    on emitting rows, so a chunk whose sampled token is discarded cannot
    corrupt a constrained row's grammar walk."""
    with jax.named_scope("sample"):     # the carry splice (llama.SCOPES)
        first = jnp.where(
            use_carry, carry_tok, tokens[:, 0]
        ).astype(jnp.int32)
        tokens = tokens.at[:, 0].set(first)
    logits, cache = llama.mixed_step(
        params, cfg, tokens, starts, q_lens, cache, page_table,
        dtype=dtype, kernels=kernels, mesh=mesh, step_tokens=step_tokens,
    )
    with_fsm = fsm_mask is not None
    with jax.named_scope("sample"):
        if with_fsm:
            fstate = jnp.where(
                use_carry, carry_fsm, ov_fsm
            ).astype(jnp.int32)
            logits = jnp.where(fsm_mask[fstate], logits, NEG_INF)
        tok = sample(
            logits, key, temps, top_k, top_p, None
        ).astype(jnp.int32)
        out = jnp.where(emits, tok, first)
        if with_fsm:
            fsm_out = jnp.where(emits, fsm_dest[fstate, tok], fstate)
        else:
            fsm_out = jnp.zeros_like(out)
    return out, cache, fsm_out


def decode_block_carry(
    params: Any,
    cfg: ModelConfig,
    # device-resident carry from the previous dispatch (or zeros):
    carry_tok: jax.Array,   # [B] int32 last sampled (not yet written) token
    carry_at: jax.Array,    # [B] int32 tokens already written to cache
    carry_eos: jax.Array,   # [B] bool  row sampled EOS at some point
    key: jax.Array,         # PRNG key (threaded through)
    # host-supplied per-dispatch inputs:
    override: jax.Array,    # [B] bool  lane newly (re)assigned: take ov_*
    ov_tok: jax.Array,      # [B] int32
    ov_at: jax.Array,       # [B] int32
    alive: jax.Array,       # [B] bool  host wants this lane running
    budgets: jax.Array,     # [B] int32 max tokens this dispatch may emit
    cache: Any,             # paged KV pytree (donated)
    page_table: jax.Array,  # [B, MaxP] pages pre-booked for the whole block
    temps: jax.Array,       # [B] float32
    top_k: jax.Array,       # [B] int32
    top_p: jax.Array,       # [B] float32
    eos_id: jax.Array,      # [] int32
    pad_id: jax.Array,      # [] int32
    n_steps: int,
    greedy: bool = False,
    dtype: jnp.dtype = jnp.bfloat16,
    kernels: Kernels = Kernels(),   # who runs what (ops.kernels)
    mesh=None,               # Mesh for the shard_mapped pallas-under-tp path
    # Device-side constrained decoding (SURVEY §7's hard part: the FSM
    # steps on device, no host sync per token). fsm_mask/fsm_dest are the
    # shared [S+1, V] tables — ROW 0 is the FREE sentinel (everything
    # allowed, dest 0) so zero-initialized states mean "unconstrained";
    # DFA state s lives at row s+1. carry_fsm/ov_fsm are per-row states.
    fsm_mask: jax.Array | None = None,
    fsm_dest: jax.Array | None = None,
    carry_fsm: jax.Array | None = None,   # [B] int32
    ov_fsm: jax.Array | None = None,      # [B] int32
) -> tuple[jax.Array, Any, tuple]:
    """``n_steps`` decode+sample steps in one program, the loop state
    living ON DEVICE across dispatches, so the host can enqueue block k+1
    before pulling block k's tokens (the pipelined engine path).

    ``greedy=True`` (trace-time) replaces the sampler with a bare argmax —
    the agent-loop default (temperature 0, reference pkg/llms/openai.go:73)
    — because even a top-k candidate scan over a 128k vocab inside the
    decode loop costs several times the decode step itself on TPU.

    Chaining dispatches through the returned carry keeps the device busy
    while the previous block's [B, n_steps] token pull and host
    bookkeeping overlap with compute.
    The ``override`` lane lets the host splice in newly admitted sequences
    (fresh token/write-offset) and ``alive`` lets it kill rows (stop
    strings, cancellations) with one-dispatch lag; everything else — EOS
    detection, per-dispatch budgets, KV writes — is decided on device.

    Returns (tokens [B, n_steps] int32, pad past a row's finish; cache; the
    new carry (tok, at, eos, fsm, key)).
    """
    tok = jnp.where(override, ov_tok, carry_tok).astype(jnp.int32)
    at = jnp.where(override, ov_at, carry_at).astype(jnp.int32)
    eos = jnp.where(override, False, carry_eos)
    act0 = alive & ~eos & (budgets > 0)
    with_fsm = fsm_mask is not None
    if with_fsm:
        fstate = jnp.where(override, ov_fsm, carry_fsm).astype(jnp.int32)
    else:
        fstate = jnp.zeros_like(tok)

    def body(carry, step_idx):
        tok, at, eos, act, fstate, cache, key = carry
        logits, cache = llama.decode_step(
            params, cfg, tok, at, cache, page_table, act,
            dtype=dtype, kernels=kernels, mesh=mesh,
        )
        with jax.named_scope("sample"):
            if with_fsm:
                # Grammar mask from the per-row DFA state: one [B, V]
                # gather, no host round trip. NEG_INF (not -inf): masked
                # logits feed a softmax in the sampled path.
                logits = jnp.where(fsm_mask[fstate], logits, -1e30)
            if greedy:
                nxt = jnp.argmax(logits, axis=-1)
            else:
                key, sub = jax.random.split(key)
                nxt = sample(logits, sub, temps, top_k, top_p, None)
            nxt = jnp.where(act, nxt, tok).astype(jnp.int32)
            emitted = jnp.where(act, nxt, pad_id).astype(jnp.int32)
            if with_fsm:
                fstate = jnp.where(act, fsm_dest[fstate, nxt], fstate)
            at = at + act.astype(jnp.int32)
            eos = eos | (act & (nxt == eos_id))
            act = act & ~eos & (step_idx + 1 < budgets)
        return (nxt, at, eos, act, fstate, cache, key), emitted

    (tok, at, eos, _, fstate, cache, key), toks = jax.lax.scan(
        body,
        (tok, at, eos, act0, fstate, cache, key),
        jnp.arange(n_steps),
    )
    return toks.T, cache, (tok, at, eos, fstate, key)
