"""Continuous-batching scheduler.

Maps concurrent agent sessions onto the engine's fixed decode batch
(BASELINE.json config 5: 32 concurrent execute sessions): an admission queue
feeds prefill as pages free up; all running sequences advance together in
decode steps; finished sequences release pages immediately, letting queued
requests enter mid-flight. Runs in a dedicated thread — JAX dispatch is
blocking — with asyncio-friendly completion events.

With ``EngineConfig.mixed_batching`` (default on) a tick with admitting
prompts runs ONE mixed dispatch — every decode lane plus up to
``max_step_tokens`` of chunked-prefill tokens in the same batch
(``Scheduler._mixed_tick`` -> ``Engine.step_mixed``) — instead of the
serialized prefill-chunk dispatch followed by a decode-block dispatch,
which streams the model weights twice per tick. The split path remains
the fallback for host-stepped rows and the flag-off configuration.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

from .. import obs
from ..utils.logger import get_logger
from ..utils.perf import get_perf_stats
from . import faults
from .engine import Engine
from .kvcache import InvalidRequest, OutOfPages, PromptTooLong
from .sampler import SamplingParams

log = get_logger("scheduler")


class RequestError(RuntimeError):
    """A failed request with an HTTP-ish status classification (400 = the
    request can never succeed, 500 = engine-side failure)."""

    def __init__(self, message: str, status: int = 500):
        super().__init__(message)
        self.status = status


@dataclass
class Request:
    prompt_ids: list[int]
    sampling: SamplingParams
    mask_fn: Callable[[list[int]], np.ndarray] | None = None
    on_token: Callable[[int], None] | None = None
    # filled by the scheduler:
    seq_id: int | None = None
    tokens: list[int] = field(default_factory=list)
    finish_reason: str = ""
    error: str = ""
    error_status: int = 500  # meaningful only when error is set
    done = None  # threading.Event, set in __post_init__
    enqueued_s: float = field(default_factory=time.perf_counter)
    # Tokens generated before an engine restart (slice-restart tolerance):
    # re-admission folds them into the prompt, and the final result is
    # generated_prefix + the post-restart generation.
    generated_prefix: list[int] = field(default_factory=list)
    # Per-token logprob entries (engine Sequence.logprob_data), populated
    # at reap when sampling.logprobs was requested; accumulates across
    # engine restarts like generated_prefix.
    logprob_data: list[dict] = field(default_factory=list)
    # Set when the request was pressure-parked to the host KV tier: its
    # re-admission is EXPECTED to restore from the host pool, so a
    # restore that falls back to re-prefill is a flight-ring anomaly.
    parked: bool = False
    # Observability: the request's span handle (obs.trace.Span). The
    # scheduler thread has no ambient contextvar from the submitting
    # thread, so the span rides the Request explicitly; queue-wait is
    # recorded here and the handle is passed into engine.begin_request
    # for the prefill/decode phase children.
    trace: Any = None

    def __post_init__(self) -> None:
        self.done = threading.Event()


# Admission precedence by SLO class: lower admits first. Unclassed
# requests rank as interactive (the default class everywhere else).
_CLASS_ADMIT_RANK = {"interactive": 0, "batch": 1, "background": 2}


def _admit_rank(req: Request) -> int:
    return _CLASS_ADMIT_RANK.get(
        obs.trace.class_of(req.trace, "interactive"), 0
    )


class Scheduler:
    def __init__(
        self,
        engine: Engine,
        admission_timeout_s: float = 120.0,
        engine_factory: Callable[[], Engine] | None = None,
        max_restarts: int = 3,
    ):
        """``engine_factory`` enables slice-restart tolerance (SURVEY §5):
        when the engine fails persistently (a restarted TPU slice, a wedged
        device runtime), the scheduler rebuilds the engine via the factory
        and RE-ADMITS every in-flight request from its retained prompt +
        tokens generated so far, instead of failing the batch. At most
        ``max_restarts`` rebuilds per scheduler lifetime; without a
        factory, persistent failure fails the in-flight requests (the
        reference's equivalent is k8s probe-driven pod restart, reference
        deploy/kubernetes/deployment-prod.yaml probes — here recovery is
        in-process and keeps queued work)."""
        self.engine = engine
        self.admission_timeout_s = admission_timeout_s
        self._engine_factory = engine_factory
        self._max_restarts = max_restarts
        self._restarts = 0
        self._queue: "queue.Queue[Request]" = queue.Queue()
        self._waiting: list[Request] = []
        self._prefilling: dict[int, Request] = {}  # begun, chunks pending
        self._running: dict[int, Request] = {}
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._wake = threading.Event()
        # Graceful-drain mode (serving/fleet): the loop exits WITHOUT
        # failing in-flight requests — drain_for_migration() then parks
        # and returns them for re-admission on another replica.
        self._draining = False

    # -- public ------------------------------------------------------------
    def start(self) -> None:
        if self._thread is None:
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None

    def submit(self, req: Request) -> Request:
        self._queue.put(req)
        self._wake.set()
        return req

    def drain_for_migration(self) -> list[Request]:
        """Graceful replica drain (serving/fleet): stop the loop WITHOUT
        failing anything, park every running session's KV to the host
        tier, and return every request that still needs tokens so the
        fleet router can re-admit them on another replica. Token loss is
        zero by construction: running sequences salvage their generated
        tokens through ``_requeue_salvaged`` (prompt += salvage, budget -=
        salvage, FSM/penalty state carried — the slice-restart flow), so
        the re-admission elsewhere continues exactly where this replica
        stopped; streaming clients keep their callbacks and are never
        re-sent a token. Requests that finished while the pipeline
        settled are reaped normally (their clients see a clean result).

        Engines without the offload tier still drain correctly — the
        salvage folds into the prompt and the target replica re-prefills
        it — they just cannot ship KV pages, so the detour costs a
        re-prefill instead of a page copy."""
        self._draining = True
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        out: list[Request] = []
        self._drain_queue()
        for sid, req in list(self._running.items()):
            parked = None
            if getattr(self.engine, "offload", None) is not None:
                try:
                    parked = self.engine.park_sequence(sid)
                except Exception:  # noqa: BLE001 - fall back to salvage
                    log.exception("drain parking of seq %d failed", sid)
            if parked is not None:
                self._running.pop(sid)
                if self._requeue_salvaged(
                    req, parked.tokens, parked.logprob_data, parked=True
                ):
                    out.append(req)
                continue
            seq = self.engine.sequences.get(sid)
            if seq is not None and seq.done:
                continue  # reaped below with full results
            # No offload tier (or parking raced): salvage host state and
            # re-admit whole; the pages are simply freed.
            partial: list[int] = []
            lp: list[dict] = []
            if seq is not None:
                lp = list(seq.logprob_data)
            try:
                partial = self.engine.finish(sid)
            except Exception:  # noqa: BLE001 - device state may be gone
                pass
            self._running.pop(sid, None)
            if self._requeue_salvaged(req, partial, lp):
                out.append(req)
        self._reap()
        for sid, req in list(self._prefilling.items()):
            try:
                self.engine.abort_request(sid)
            except Exception:  # noqa: BLE001
                pass
            req.seq_id = None
            req.enqueued_s = time.perf_counter()
            out.append(req)
        self._prefilling.clear()
        out.extend(self._waiting)
        self._waiting = []
        flush = getattr(self.engine, "offload_flush", None)
        if flush is not None:
            try:
                flush()  # land the parked pages in the host pool
            except Exception:  # noqa: BLE001 - best-effort
                pass
        return out

    def complete(
        self,
        prompt_ids: list[int],
        sampling: SamplingParams,
        mask_fn=None,
        on_token=None,
        timeout_s: float = 600.0,
    ) -> list[int]:
        """Blocking convenience: submit and wait for the generated tokens."""
        req = Request(prompt_ids, sampling, mask_fn=mask_fn, on_token=on_token)
        self.submit(req)
        if not req.done.wait(timeout_s):
            raise TimeoutError("generation timed out")
        if req.error:
            raise RequestError(req.error, req.error_status)
        return req.tokens

    # -- loop --------------------------------------------------------------
    def _drain_queue(self) -> None:
        while True:
            try:
                self._waiting.append(self._queue.get_nowait())
            except queue.Empty:
                return

    def _try_admit(self) -> None:
        """Move waiting requests into the prefilling state while page
        budget and batch slots allow. Only the cheap page allocation
        happens here (engine.begin_request); the device work is advanced
        one chunk per loop tick by ``_advance_prefill`` so long prompts
        cannot stall running decodes.

        Admission is class-fair, not FIFO: interactive requests admit
        ahead of batch (and batch ahead of background) within the same
        tick, so a fan-out's thousand batch children queued an instant
        before an interactive request cannot inflate its TTFT by the
        whole wave. The sort is stable — arrival order is preserved
        within a class."""
        if len(self._waiting) > 1:
            self._waiting.sort(key=_admit_rank)
        still: list[Request] = []
        now = time.perf_counter()
        for req in self._waiting:
            occupied = len(self._running) + len(self._prefilling)
            if occupied >= self.engine.cfg.max_batch_size:
                still.append(req)
                continue
            if now - req.enqueued_s > self.admission_timeout_s:
                req.error = "admission timed out (engine saturated)"
                obs.ENGINE_REQUESTS.inc(outcome="timeout")
                obs.CLASS_REQUESTS.inc(**{
                    "class": obs.trace.class_of(req.trace, "interactive"),
                    "outcome": "timeout",
                })
                obs.flight.anomaly(
                    "request_error", error=req.error,
                    request_id=obs.flight.request_id_of(req.trace),
                )
                req.done.set()
                continue
            def _begin(r: Request) -> int:
                # One opsagent_admission_seconds observation an ATTEMPT: an
                # admission that ends in OutOfPages is made again on a
                # later tick, the whole match with it.
                t0 = time.perf_counter()
                outcome = "rejected"
                try:
                    faults.maybe_raise(
                        "sched.out_of_pages", OutOfPages,
                        "injected OutOfPages storm",
                    )
                    seq_id = self.engine.begin_request(
                        r.prompt_ids,
                        r.sampling,
                        mask_fn=r.mask_fn,
                        stream=r.on_token,
                        trace=r.trace,
                        expect_restore=r.parked,
                    )
                    outcome = "admitted"
                    return seq_id
                except OutOfPages:
                    outcome = "out_of_pages"
                    raise
                finally:
                    obs.ADMISSION_SECONDS.observe(
                        time.perf_counter() - t0, outcome=outcome)

            try:
                try:
                    seq_id = _begin(req)
                except OutOfPages:
                    # Offload tier: instead of queueing the new prompt
                    # behind pages a cold session is pinning, park the
                    # coldest running session to host RAM (it restores
                    # instead of re-prefilling when it comes back) and
                    # retry the admission once.
                    if not self._park_coldest():
                        raise
                    seq_id = _begin(req)
            except OutOfPages:
                # Transient: pages will free as running sequences finish.
                still.append(req)
                continue
            except PromptTooLong as e:
                # Permanent: reject immediately with a clear error.
                req.error = str(e)
                req.error_status = 400
                req.done.set()
                continue
            except InvalidRequest as e:
                # Malformed request (e.g. empty prompt): the client's fault.
                # Plain ValueErrors from engine internals stay 500 below.
                req.error = f"admission failed: {e}"
                req.error_status = 400
                req.done.set()
                continue
            except Exception as e:  # noqa: BLE001 - surfaced on the request
                req.error = f"admission failed: {e}"
                req.done.set()
                continue
            req.seq_id = seq_id
            self._prefilling[seq_id] = req
            with obs.phase("admit", part="account"):
                wait_s = now - req.enqueued_s
                get_perf_stats().record_metric(
                    "scheduler.queue_wait", wait_s * 1e3, "ms"
                )
                obs.QUEUE_WAIT_SECONDS.observe(wait_s)
                obs.attribution.record_goodput(
                    wait_s, "queued",
                    slo_class=obs.trace.class_of(req.trace),
                )
                if req.trace is not None:
                    req.trace.child("queue_wait", req.enqueued_s, now)
        self._waiting = still

    def _advance_prefill(self) -> None:
        """Run ONE prefill chunk for a BATCH of admitting requests: the
        oldest one plus up to prefill_batch-1 more whose next chunk
        compiles into the same bucket, in a single dispatch
        (engine.prefill_batch). One chunk-batch per tick means long
        prompts interleave with decode blocks instead of monopolizing the
        device, while concurrent admissions (BASELINE config 5) share
        dispatches instead of queueing one per tick."""
        if not self._prefilling:
            return
        first = next(iter(self._prefilling))
        batch = [first]
        try:
            with obs.phase("plan", part="chunks"):
                bucket = self.engine.next_prefill_bucket(first)
                for sid in self._prefilling:
                    if len(batch) >= self.engine.cfg.prefill_batch:
                        break
                    if sid != first and (
                        self.engine.next_prefill_bucket(sid) == bucket
                    ):
                        batch.append(sid)
            results = self.engine.prefill_batch(batch)
        except Exception as e:  # noqa: BLE001 - engine cleaned up already
            for sid in batch:
                self._fail_admission(sid, e)
            return
        for sid, res in results.items():
            if isinstance(res, Exception):
                # Row-local failure (raising stream callback / mask_fn):
                # only this request fails, matching the decode path's
                # one-bad-apple isolation.
                self._fail_admission(sid, res)
            elif res:
                self._running[sid] = self._prefilling.pop(sid)

    def _mixed_tick(self) -> bool:
        """The unified mixed prefill+decode tick (EngineConfig
        .mixed_batching): ONE engine dispatch advances every running
        decode lane by a token AND seats prefill chunks for the oldest
        admitting prompts, under a token budget — decode lanes are funded
        first (1 token each), the remaining ``max_step_tokens`` budget
        goes to admitting prompts in arrival order. Versus the split
        ``_advance_prefill(); step_block()`` tick this streams the model
        weights ONCE per tick instead of twice, and an admitting prompt
        advances every tick instead of waiting out a full decode block —
        TTFT is no longer quantized to decode-block boundaries.

        Returns True when a mixed dispatch ran (the caller skips the split
        tick); False routes the tick to the split path — no admitting
        prompts, some involved row needs host-side per-token work
        (constrained mask / logprobs / logit bias), or the budget left no
        room for a chunk.

        With ``EngineConfig.async_depth`` > 1 the tick goes through the
        one-step-lookahead pipeline instead (``_async_mixed_tick``):
        dispatches run ahead, results lag, and this method's synchronous
        body remains the depth-1 behavior."""
        eng = self.engine
        if not getattr(eng.cfg, "mixed_batching", False):
            return False
        if getattr(eng.cfg, "async_depth", 1) > 1:
            if self._async_mixed_tick():
                return True
            # The lookahead lane passed on the tick (pure decode, hosted
            # rows, nothing admitting): rows falling back to the block
            # pipeline still get the grammar fast-forward below — the
            # async planner only covers rows IT dispatches.
            if getattr(eng.cfg, "grammar_ffwd", False) and self._running:
                with obs.phase("plan", part="ffwd"):
                    eng.ffwd_step(sorted(self._running))
            return False
        # Grammar fast-forward (depth-1 sync lane): splice forced-token
        # runs for constrained rows BEFORE the hosted-row bail below routes
        # the tick to the split path — constrained rows are always
        # mixed_hosted, so this is their only mixed-family entry point.
        # The engine pre-scans without touching the block pipeline: rows
        # with device-resident in-flight tokens are left alone (their host
        # token lists are stale), so the fast-forward engages at
        # settle/admission boundaries; the async lane (depth > 1) engages
        # at every plan point it dispatches.
        if getattr(eng.cfg, "grammar_ffwd", False) and self._running:
            with obs.phase("plan", part="ffwd"):
                eng.ffwd_step(sorted(self._running))
        if not self._prefilling:
            return False
        with obs.phase("plan", part="route"):
            for sid in list(self._running) + list(self._prefilling):
                if eng.mixed_hosted(sid):
                    return False
        decode_ids = sorted(
            sid for sid in self._running
            if sid in eng.sequences and not eng.sequences[sid].done
        )
        budget = eng.cfg.max_step_tokens - len(decode_ids)
        rows_left = eng.cfg.max_batch_size - len(decode_ids)
        cap = eng.cfg.mixed_buckets[-1]
        chunks: dict[int, int] = {}
        # dict order is admission order: oldest admitting prompts first.
        for sid in self._prefilling:
            if budget <= 0 or rows_left <= 0:
                break
            try:
                done, total = eng.prefill_progress(sid)
            except KeyError:
                continue  # raced with a failure path; reaped elsewhere
            c = min(total - done, budget, cap)
            if c <= 0:
                continue
            chunks[sid] = c
            budget -= c
            rows_left -= 1
        if not chunks:
            return False
        try:
            _, prefill_out = eng.step_mixed(decode_ids, chunks)
        except Exception as e:  # noqa: BLE001 - engine cleaned up already
            # The engine dropped every chunk admission before re-raising;
            # fail those requests, then let the loop's failure accounting
            # see the dispatch error (persistent engine failures must
            # still trigger recovery).
            for sid in chunks:
                self._fail_admission(sid, e)
            raise
        for sid, res in prefill_out.items():
            if isinstance(res, Exception):
                self._fail_admission(sid, res)
            elif res:
                self._running[sid] = self._prefilling.pop(sid)
        return True

    def _fold_async_prefill(self, prefill_out: dict) -> None:
        """Apply committed async admission outcomes: completed prompts
        move to running, row-local failures fail just their request.
        ``False`` entries (chunk landed, prompt unfinished) are no-ops."""
        for sid, res in prefill_out.items():
            if isinstance(res, Exception):
                self._fail_admission(sid, res)
            elif res:
                req = self._prefilling.pop(sid, None)
                if req is not None:
                    self._running[sid] = req

    def _async_mixed_tick(self) -> bool:
        """The one-step-lookahead mixed tick (EngineConfig.async_depth > 1,
        serving/async_runtime.py): plan and DISPATCH tick t+1 before tick
        t's tokens are pulled — the engine keeps decode-lane feedback
        device-resident, so the host work this loop does between
        dispatches (reaping, admission planning, and the engine-side
        detokenize/stop-scan/streaming at commit) overlaps device compute.
        Results returned by the engine lag the dispatch by up to depth-1
        ticks; admission completions are folded in whenever they surface.

        Returns True when the tick was consumed by the async lane
        (dispatch or pipeline settle); False routes to the sync paths —
        no admitting work and nothing in flight (pure decode belongs to
        the block pipeline), or an involved row needs a hosted lane."""
        eng = self.engine
        with obs.phase("plan", part="route"):
            # Pick up results committed by internal pipeline settles
            # (parking, warmup, sync-lane entry points) since the last tick.
            _, p_out = eng.async_take_results()
            self._fold_async_prefill(p_out)
            # Grammar fast-forward keeps dense-table constrained rows in the
            # async lane even for PURE decode: the planner splices forced
            # runs at every dispatch point, which the block pipeline (host
            # token lists stale behind in-flight blocks) cannot do. Per-tick
            # host overhead loses to block batching only when forced states
            # are rare — a schema-constrained row is exactly where they are
            # not.
            ffwd_decode = (
                getattr(eng.cfg, "grammar_ffwd", False)
                and any(
                    sid in eng.sequences and not eng.sequences[sid].done
                    and eng.async_row_fsm(sid) is not None
                    for sid in self._running
                )
            )
            if not self._prefilling and not eng.async_pending() \
                    and not ffwd_decode:
                return False
            # Hosted rows (and mixed-schema constrained batches) route the
            # tick to the sync lanes — settle the pipeline first so the split
            # path sees current host state.
            fsm_seen = None
            for sid in list(self._running) + list(self._prefilling):
                hosted = eng.mixed_async_hosted(sid)
                mismatch = False
                if not hosted:
                    f = eng.async_row_fsm(sid)
                    if f is not None:
                        if fsm_seen is not None and f is not fsm_seen:
                            mismatch = True
                        fsm_seen = f
                if hosted or mismatch:
                    obs.ASYNC_FALLBACKS.inc(
                        reason="hosted" if hosted else "fsm_mismatch"
                    )
                    if hosted and getattr(eng.cfg, "grammar_ffwd", False):
                        # A hosted row (host mask / no dense tables /
                        # logprobs / bias) also cannot fast-forward; the
                        # distinct reason label separates "can't ffwd" from
                        # "can't async" (counted once per sequence).
                        eng.note_ffwd_ineligible(sid)
                    _, p_out = eng.async_drain()
                    self._fold_async_prefill(p_out)
                    return False
        with obs.phase("plan", part="chunks"):
            decode_ids = sorted(
                sid for sid in self._running
                if sid in eng.sequences and not eng.sequences[sid].done
            )
            budget = eng.cfg.max_step_tokens - len(decode_ids)
            rows_left = eng.cfg.max_batch_size - len(decode_ids)
            cap = eng.cfg.mixed_buckets[-1]
            chunks: dict[int, int] = {}
            for sid in self._prefilling:
                if budget <= 0 or rows_left <= 0:
                    break
                try:
                    # Progress here is PLAN progress: chunks already in
                    # flight count as done, so a prompt is never re-offered.
                    done, total = eng.prefill_progress(sid)
                except KeyError:
                    continue  # completion still in flight, or a failure path
                c = min(total - done, budget, cap)
                if c <= 0:
                    continue
                chunks[sid] = c
                budget -= c
                rows_left -= 1
        if not chunks:
            if not ffwd_decode:
                if not eng.async_pending():
                    return False
                # Every admitting prompt is fully planned (or the budget
                # is spent) and only commits remain: settle the pipeline
                # so the completions land, then let the next tick route
                # pure decode to the block pipeline.
                _, p_out = eng.async_drain()
                self._fold_async_prefill(p_out)
                return True
            if not decode_ids:
                return False
        try:
            _, p_out = eng.step_mixed_async(decode_ids, chunks)
        except Exception as e:  # noqa: BLE001 - engine cleaned up already
            # The engine dropped THIS tick's chunk admissions before
            # re-raising (earlier in-flight ticks were salvaged); fail
            # those requests, then let the loop's failure accounting see
            # the dispatch error.
            for sid in chunks:
                self._fail_admission(sid, e)
            raise
        self._fold_async_prefill(p_out)
        return True

    def _park_coldest(self) -> bool:
        """Pressure-eviction policy (offload tier): pick the coldest
        running session — LRU by last produced token — park its KV to the
        host pool (engine.park_sequence), and re-queue its request with
        the generated tokens salvaged into the prompt, exactly like the
        slice-restart flow. The re-admission restores the pages from the
        host pool, so the detour costs two page copies, not a re-prefill.
        Returns True when a session was parked (the caller retries its
        admission against the freed pages)."""
        eng = self.engine
        if getattr(eng, "offload", None) is None or not self._running:
            return False
        best: tuple[float, int] | None = None
        for sid in self._running:
            seq = eng.sequences.get(sid)
            if seq is None or seq.done:
                continue
            last = seq.last_tok_s or seq.started_s
            if best is None or last < best[0]:
                best = (last, sid)
        if best is None:
            return False
        sid = best[1]
        try:
            parked = eng.park_sequence(sid)
        except Exception:  # noqa: BLE001 - parking is best-effort
            log.exception("pressure parking of seq %d failed", sid)
            return False
        if parked is None:
            return False  # finished while the pipeline settled: reap it
        req = self._running.pop(sid)
        if self._requeue_salvaged(req, parked.tokens, parked.logprob_data,
                                  parked=True):
            # Cold sessions go to the BACK of the queue: the new prompt
            # the parking made room for admits first (that is the point).
            self._waiting.append(req)
        return True

    def _requeue_salvaged(
        self,
        req: Request,
        partial: list[int],
        logprob_data: list[dict],
        parked: bool = False,
    ) -> bool:
        """Fold a salvaged generation into the request so its re-admission
        continues where it stopped: prompt += salvage, budget -= salvage,
        penalties keep counting the salvage as output, a constrained
        mask_fn keeps walking its FSM from where it was, and streaming
        clients notice nothing (delivered tokens are not re-sent). Shared
        by engine-restart recovery and pressure parking. Returns False
        when the budget is exhausted (the request was finished instead of
        re-queued)."""
        from dataclasses import replace as dc_replace

        req.logprob_data = req.logprob_data + logprob_data[: len(partial)]
        req.generated_prefix = req.generated_prefix + partial
        # sampling.max_tokens was already reduced by earlier salvages;
        # subtract only THIS one's.
        budget = req.sampling.max_tokens - len(partial)
        if budget <= 0:
            req.tokens = req.generated_prefix
            req.finish_reason = "length"
            req.done.set()
            return False
        req.prompt_ids = req.prompt_ids + partial
        req.sampling = dc_replace(
            req.sampling,
            max_tokens=budget,
            # Salvaged tokens fold into the prompt, but penalty counting
            # must keep treating them as generated output.
            penalty_history=tuple(req.generated_prefix),
        )
        if req.mask_fn is not None and partial:
            # Wrap with only THIS salvage: the inner fn already prepends
            # earlier salvages, so prepending the cumulative prefix would
            # feed the FSM earlier tokens twice.
            inner = req.mask_fn
            req.mask_fn = (
                lambda toks, _p=list(partial), _f=inner: _f(_p + toks)
            )
        req.seq_id = None
        req.parked = parked or req.parked
        # Time already spent generating must not count against the
        # ADMISSION timeout of the re-admission.
        req.enqueued_s = time.perf_counter()
        return True

    def _fail_admission(self, sid: int, e: Exception) -> None:
        req = self._prefilling.pop(sid, None)
        if req is None:
            return
        req.error = f"admission failed: {e}"
        if isinstance(e, (InvalidRequest, PromptTooLong)):
            req.error_status = 400
        obs.ENGINE_REQUESTS.inc(outcome="admission_failed")
        obs.CLASS_REQUESTS.inc(**{
            "class": obs.trace.class_of(req.trace, "interactive"),
            "outcome": "admission_failed",
        })
        obs.flight.anomaly(
            "request_error", seq_id=sid, error=str(e),
            request_id=obs.flight.request_id_of(req.trace),
        )
        req.done.set()

    def _reap(self) -> None:
        finished = [
            sid for sid, req in self._running.items()
            if self.engine.sequences[sid].done
        ]
        for sid in finished:
            req = self._running.pop(sid)
            seq = self.engine.sequences[sid]
            req.finish_reason = seq.finish_reason
            req.logprob_data = req.logprob_data + seq.logprob_data
            with obs.phase("reap", part="finish"):
                req.tokens = req.generated_prefix + self.engine.finish(sid)
            if req.finish_reason == "error":
                # The engine terminated this sequence on a raising stream
                # callback (client went away mid-stream). Only THIS request
                # fails; the rest of the batch keeps decoding.
                req.error = "stream callback failed"
            with obs.phase("reap", part="account"):
                obs.ENGINE_REQUESTS.inc(
                    outcome="error" if req.error else "completed"
                )
                obs.CLASS_REQUESTS.inc(**{
                    "class": obs.trace.class_of(req.trace, "interactive"),
                    "outcome": "error" if req.error else "completed",
                })
                if len(seq.tokens) > 1:
                    # the ticks a token takes, summed over requests
                    obs.REQUEST_DECODE_TICKS.inc(
                        max(0, seq.last_tok_tick - seq.first_tok_tick))
                    obs.REQUEST_DECODE_TOKENS.inc(len(seq.tokens) - 1)
                if req.error:
                    obs.flight.anomaly(
                        "request_error", seq_id=sid, error=req.error,
                        request_id=obs.flight.request_id_of(req.trace),
                    )
            req.done.set()

    def _recover(self) -> None:
        """Slice-restart tolerance: rebuild the engine and re-admit every
        in-flight request from retained host state (SURVEY §5's "queue
        drain + re-prefill from retained prompts").

        For each running sequence, whatever tokens the dying engine's host
        state still exposes are salvaged into ``generated_prefix`` and the
        request re-enters the admission queue (``_requeue_salvaged``: the
        re-prefill rebuilds its full context, prefix cache making it cheap
        when pages survive). Streaming clients notice nothing:
        already-delivered tokens are not re-sent."""
        self._restarts += 1
        log.error(
            "engine restart %d/%d: rebuilding device state, re-admitting "
            "%d running + %d prefilling requests",
            self._restarts, self._max_restarts,
            len(self._running), len(self._prefilling),
        )
        obs.flight.anomaly(
            "engine_restart", restart=self._restarts,
            max_restarts=self._max_restarts,
            running=len(self._running), prefilling=len(self._prefilling),
        )
        salvaged: list[Request] = []
        for sid, req in list(self._running.items()):
            partial: list[int] = []
            seq_obj = self.engine.sequences.get(sid)
            try:
                partial = self.engine.finish(sid)
            except Exception:  # noqa: BLE001 - device state may be gone
                pass
            # Slice logprobs to the tokens actually salvaged: if finish()
            # raised, partial is empty and keeping the entries would
            # misalign every post-restart token's logprobs.
            if self._requeue_salvaged(
                req, partial,
                seq_obj.logprob_data if seq_obj is not None else [],
            ):
                salvaged.append(req)
        self._running.clear()
        for sid, req in list(self._prefilling.items()):
            # Not decoding yet: nothing generated, just re-admit whole.
            req.seq_id = None
            req.enqueued_s = time.perf_counter()
            salvaged.append(req)
        self._prefilling.clear()
        # Oldest first so re-admitted work keeps its queue position.
        self._waiting = salvaged + self._waiting
        # Release the dead engine's device buffers BEFORE building the
        # replacement: params + KV cache can be most of HBM, and a wedged
        # (not torn-down) runtime would otherwise hold both engines at
        # once and OOM the rebuild. The old engine stays referenced for
        # its host-side state (allocator, sequences) in case the rebuild
        # fails — admission is host-only, so queued work survives.
        for buf in (
            "params", "cache", "_carry",
            "_async_carry", "_async_fsm_carry",
        ):
            try:
                setattr(self.engine, buf, None)
            except Exception:  # noqa: BLE001
                pass
        try:
            self.engine = self._engine_factory()
        except Exception:  # noqa: BLE001 - slice may still be restarting
            # Keep the old engine reference: admission is host-side, so
            # queued work is not insta-failed; the next persistent device
            # failure triggers another recovery attempt (until the
            # restart budget runs out).
            log.exception(
                "engine rebuild failed; keeping queued work for the next "
                "recovery attempt"
            )

    def _loop(self) -> None:
        log.info("scheduler loop started (batch=%d)", self.engine.cfg.max_batch_size)
        consecutive_failures = 0
        while not self._stop.is_set():
            try:
                # The loop is always in exactly one obs.phase (the table
                # in docs/observability.md): admit, plan, reap and idle
                # here; the engine carves dispatch, wait and commit out
                # of plan where it enqueues, blocks on and folds a step,
                # and a dispatch out of admit where an admission restores
                # a state snapshot (Engine._copy_state). The parts of a
                # phase are named where the work is (the same table).
                with obs.phase("admit", part="drain"):
                    self._drain_queue()
                with obs.phase("admit"):
                    self._try_admit()
                if self._running or self._prefilling:
                    obs.TICKS.inc()
                    # One tick's host work: the work phases' seconds since
                    # the last tick was counted, and the tick number that
                    # the engine stamps a sequence's tokens with.
                    obs.TICK_HOST_WORK_SECONDS.observe(obs.take_host_work())
                    self.engine.sched_tick = (
                        getattr(self.engine, "sched_tick", 0) + 1)
                    # Only counted with work in flight: idle ticks spin
                    # at an arbitrary rate, which would make hit-count
                    # fault selectors wall-clock-dependent.
                    faults.maybe_raise(
                        "sched.step_fault", RuntimeError,
                        "injected scheduler step fault",
                    )
                # Mixed tick first: one dispatch covers decode AND a
                # prefill chunk (one weight stream). Falls back to the
                # split prefill-then-decode tick when it cannot run.
                with obs.phase("plan"):
                    mixed = self._mixed_tick()
                    if not mixed:
                        self._advance_prefill()
                with obs.phase("reap"):
                    self._reap()
                    idle = not self._running and not self._prefilling
                    if idle:
                        # The mixed-tick cadence breaks here — the wait
                        # must not be observed as host gap.
                        gap_break = getattr(
                            self.engine, "mixed_gap_break", None
                        )
                        if gap_break is not None:
                            gap_break()
                        # Land pending device->host page copies (the
                        # offload double buffer's drain side), then wait.
                        flush = getattr(self.engine, "offload_flush", None)
                        if flush is not None:
                            try:
                                flush()
                            except Exception:  # noqa: BLE001 - best-effort
                                pass
                if idle:
                    with obs.phase("idle"):
                        self._wake.wait(timeout=0.05)
                        self._wake.clear()
                    continue
                if not self._running:
                    continue  # keep advancing admission chunks
                if not mixed:
                    with obs.phase("plan"):
                        self.engine.step_block(sorted(self._running))
                    with obs.phase("reap"):
                        self._reap()
                consecutive_failures = 0
            except Exception as e:  # noqa: BLE001 - the loop must survive
                # A raising stream callback surfaces here after the engine
                # already marked its sequence done/"error" — _reap fails
                # just that request. Only a persistently failing engine
                # (no per-seq attribution, no progress) fails the batch.
                log.exception("scheduler step failed")
                before = len(self._running)
                try:
                    with obs.phase("reap"):
                        self._reap()
                except Exception:  # noqa: BLE001
                    pass
                if len(self._running) < before:
                    # Attributed: the offending request(s) were reaped —
                    # that IS progress, not an engine failure.
                    consecutive_failures = 0
                    continue
                consecutive_failures += 1
                if consecutive_failures < 3:
                    continue
                if (
                    self._engine_factory is not None
                    and self._restarts < self._max_restarts
                ):
                    self._recover()
                    consecutive_failures = 0
                    continue
                log.error("engine failing persistently; failing in-flight requests")
                obs.flight.anomaly(
                    "request_error",
                    error=f"engine failing persistently: {e}",
                    failed_requests=len(self._running),
                )
                for sid, req in list(self._running.items()):
                    req.error = f"engine step failed: {e}"
                    # Earlier restarts' salvage was already streamed to the
                    # client; keep it in the result even if the dead
                    # engine's finish() raises.
                    req.tokens = list(req.generated_prefix)
                    try:
                        req.tokens = (
                            req.generated_prefix + self.engine.finish(sid)
                        )
                    except Exception:  # noqa: BLE001
                        pass
                    req.done.set()
                self._running.clear()
                consecutive_failures = 0
        if self._draining:
            # Graceful drain: leave every in-flight request intact for
            # drain_for_migration() to park and hand to the fleet router.
            log.info(
                "scheduler loop stopped for drain (%d running, %d "
                "prefilling, %d waiting retained)",
                len(self._running), len(self._prefilling),
                len(self._waiting),
            )
            return
        # drain on shutdown
        for req in self._waiting:
            req.error = "scheduler stopped"
            req.done.set()
        for sid, req in list(self._prefilling.items()):
            try:
                self.engine.abort_request(sid)
            except Exception:  # noqa: BLE001
                pass
            req.error = "scheduler stopped"
            req.done.set()
        self._prefilling.clear()
        for sid, req in list(self._running.items()):
            req.tokens = req.generated_prefix + self.engine.finish(sid)
            req.error = "scheduler stopped"
            req.done.set()
        self._running.clear()
        log.info("scheduler loop stopped")
