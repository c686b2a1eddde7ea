"""One-step-lookahead async mixed serving ticks.

PERF.md's r04 trace work showed the raw device loop does ~4775 tok/s/chip
while the full serving stack delivers a tenth of that — because every
mixed scheduler tick is synchronous: dispatch ``step_mixed``, block on
``np.asarray(toks)``, then do ALL host work (detokenize, stop-string
scan, streaming callbacks, trie bookkeeping, admission planning) while
the device sits idle. This module makes the mixed prefill+decode path a
two-deep asynchronous pipeline:

- **Plan phase (runs ahead)**: build tick t+1's batch and enqueue its
  dispatch BEFORE tick t's tokens are pulled. The decode lanes' input
  tokens never visit the host — they ride a device-resident carry (the
  previous dispatch's sampled-token output, exactly the trick
  ``decode_loop.decode_block_carry`` plays for pure block decode).
- **Commit phase (lags one step)**: pull tick t's tokens and run the
  host post-processing while tick t+1 executes on device.

Consequences the rest of the engine absorbs:

- Stop-string and EOS detection lag one tick: a finished row's single
  overshoot token is discarded at commit and its page booking rolled
  back (``opsagent_async_overshoot_tokens_total`` counts them).
  ``max_tokens`` finishes exactly — the planner never books past the
  budget — so only data-dependent finishes pay the overshoot.
- A prompt whose final chunk is in flight keeps decoding through
  *lookahead lanes*: its first sampled token exists only in the device
  carry, so the runtime seats it as a carry-fed decode row before the
  scheduler even learns the admission completed.
- Constrained rows ride the async lane only when their FSM has dense
  device tables (``constrained.device_table_fsm``): the grammar mask
  comes from on-device state (``decode_loop.mixed_step_carry``).
  Everything else — hosted masks, logprobs, logit bias — falls back to
  the existing sync lanes (the scheduler routes those ticks away before
  they get here).

The runtime operates on the Engine's state under the Engine's lock; the
Engine owns one instance and exposes it as ``step_mixed_async`` /
``async_drain`` (engine.py).
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..utils.logger import get_logger
from ..utils.perf import get_perf_stats
from ..utils.profiling import annotate
from .constrained import device_table_fsm
from .kvcache import OutOfPages

log = get_logger("async_runtime")


@dataclass
class _Tick:
    """One dispatched-but-uncommitted mixed tick."""

    toks_d: Any                       # device [B] sampled tokens (= carry)
    decode: list = field(default_factory=list)   # [(seq_id, lane)]
    chunks: list = field(default_factory=list)   # [(seq_id, lane, done, c)]
    # Grammar fast-forward pre-accepts: seq_id -> forced tokens this
    # dispatch appended BEFORE its sampled token (committed in order
    # ahead of the pull; see _dispatch's ffwd planning).
    ffwd: dict = field(default_factory=dict)
    # The step clock's ticket (engine-wide step number, enqueue time, the
    # width its dense segments ran): the number is the tick id that the
    # flight event, the phase spans and the per-request span children carry.
    ticket: tuple[int, float, str] = (0, 0.0, "")
    bucket: int = 0


class AsyncMixedRuntime:
    """Plan/commit machinery for the async mixed pipeline. Every method
    assumes the caller holds the engine lock (the Engine wrappers do)."""

    def __init__(self, engine):
        self.eng = engine
        self._pending: deque[_Tick] = deque()
        # Previous dispatch's seating: carry continuity requires a decode
        # row to have emitted in the immediately preceding dispatch in the
        # same lane — anything else re-seats from host state.
        self._prev_lane: dict[int, int] = {}
        self._prev_emitted: set[int] = set()
        # Tokens sampled but not yet committed, per sequence: the budget
        # guard (max_tokens is never overshot) and the carry-break check.
        self._inflight_toks: dict[int, int] = {}
        # Prompts whose FINAL chunk is dispatched but uncommitted: they
        # ride subsequent ticks as carry-fed lookahead decode lanes.
        self._finishing: set[int] = set()
        # Committed results awaiting pickup (internal settles — parking,
        # warmup, sync-lane fallbacks — commit into this buffer so a
        # finished admission can never be lost between scheduler ticks).
        self._results: tuple[dict, dict] = ({}, {})

    # -- public surface (via Engine wrappers) -------------------------------
    @property
    def pending(self) -> int:
        return len(self._pending)

    def take_results(self) -> tuple[dict[int, list[int]], dict[int, Any]]:
        d, p = self._results
        self._results = ({}, {})
        return d, p

    def step(
        self, decode_ids: list[int], prefill_chunks: dict[int, int]
    ) -> tuple[dict[int, list[int]], dict[int, Any]]:
        """Dispatch one mixed tick (plan phase) and commit every tick past
        the configured lookahead depth. Returns the committed results so
        far — which, at depth > 1, describe EARLIER ticks than the one
        just dispatched."""
        depth = max(1, getattr(self.eng.cfg, "async_depth", 1))
        dispatched = False
        if decode_ids or prefill_chunks or self._finishing:
            dispatched = self._dispatch(decode_ids, prefill_chunks)
        while len(self._pending) >= depth:
            self._commit_oldest()
        if not dispatched and self._pending:
            # Nothing was dispatched (every row finished, budget-covered,
            # or filtered): a commit still guarantees progress — the
            # caller must never spin on a pipeline only it can drain.
            self._commit_oldest()
        return self.take_results()

    def drain_decode(self) -> dict[int, list[int]]:
        """Flush, then hand back ONLY the decode tokens; buffered prefill
        completions stay for ``take_results`` (the scheduler still needs
        them). Engine.drain's pickup form."""
        self.flush()
        d, p = self._results
        self._results = ({}, p)
        return d

    def flush(self) -> None:
        """Commit everything in flight and drop the carry seating: the
        next dispatch re-seats every row from (now current) host state.
        Called before any sync-lane engine path touches shared state."""
        while self._pending:
            self._commit_oldest()
        self._prev_lane = {}
        self._prev_emitted = set()
        self._inflight_toks.clear()
        self._finishing.clear()

    # -- plan phase ----------------------------------------------------------
    def _dispatch(
        self, decode_ids: list[int], prefill_chunks: dict[int, int]
    ) -> bool:
        """One tick's plan and its enqueue. The parts of ``plan`` here
        (docs/observability.md "Tick phases"): ``rows`` (which decode rows
        ride, their one-token bookings), ``chunks``, ``ffwd``, ``lanes``,
        ``arrays`` (with the allocator's share summed into ``pages``),
        ``account`` (what only observes) and, after the call, ``book``."""
        eng = self.eng
        with obs.phase("plan", part="rows"):
            decode = self._rows(decode_ids)
        with obs.phase("plan", part="chunks"):
            chunk_info, smax, room = self._chunks(prefill_chunks, len(decode))
        with obs.phase("plan", part="ffwd"):
            ffwd_plan, room = self._ffwd_plans(decode, room)
        for _sid, (_a, _pre, _st) in ffwd_plan.items():
            smax = max(smax, 1 + len(_pre))
        if not decode and not chunk_info:
            return False
        S = eng._mixed_bucket(smax)
        with obs.phase("plan", part="lanes"):
            lane_of, continuing = self._assign_lanes(decode, chunk_info)
        with eng._building_arrays():
            host, fsm_obj, dec_rows, chk_rows = self._arrays(
                S, decode, chunk_info, ffwd_plan, lane_of, continuing)
        return self._enqueue(
            S, host, fsm_obj, dec_rows, chk_rows, ffwd_plan)

    def _rows(self, decode_ids: list[int]) -> list:
        """The decode rows of this dispatch, each with the token it is
        about to write booked."""
        eng = self.eng
        decode = [
            eng.sequences[s] for s in decode_ids
            if s in eng.sequences and not eng.sequences[s].done
        ]
        # Lookahead lanes: prompts whose final chunk is in flight decode
        # through the carry without waiting for the scheduler to learn
        # the admission completed. Continuity required — their only token
        # lives in the device carry.
        seen = {s.seq_id for s in decode}
        for sid in sorted(self._finishing):
            if sid in seen:
                continue
            s = eng.sequences.get(sid)
            if (
                s is not None and not s.done
                and sid in self._prev_lane and sid in self._prev_emitted
            ):
                decode.append(s)
        # Carry-continuity check: a decode row with uncommitted tokens
        # MUST have emitted in the previous dispatch (its next input token
        # exists only in the device carry). A skipped tick means the
        # caller reordered rows under us — settle so host state is
        # current, then everything re-seats fresh.
        for s in decode:
            if self._inflight_toks.get(s.seq_id, 0) and (
                s.seq_id not in self._prev_lane
                or s.seq_id not in self._prev_emitted
            ):
                obs.ASYNC_FALLBACKS.inc(reason="carry_break")
                self.flush()
                break
        # Budget guard: never dispatch a token max_tokens cannot accept —
        # "length" finishes exactly, with no overshoot to discard.
        decode = [
            s for s in decode
            if not s.done
            and len(s.tokens) + self._inflight_toks.get(s.seq_id, 0)
            < s.params.max_tokens
        ]
        # Book the token each decode lane is about to write. On a dry
        # pool, committing the pipeline first can roll finished rows'
        # bookings back — retry once before truncating (step()'s flow).
        grown: list = []
        for s in decode:
            try:
                eng.alloc.extend(s.seq_id, 1)
                grown.append(s)
                continue
            except OutOfPages:
                pass
            self.flush()
            if s.done:
                continue
            try:
                eng.alloc.extend(s.seq_id, 1)
                grown.append(s)
            except OutOfPages:
                s.done = True
                s.finish_reason = "length"
                obs.PREEMPTIONS.inc()
                obs.flight.record("preemption", seq_id=s.seq_id)
                log.warning(
                    "seq %d truncated: KV page budget exhausted", s.seq_id
                )
        return [s for s in grown if not s.done]

    def _chunks(
        self, prefill_chunks: dict[int, int], n_decode: int
    ) -> tuple[list, int, int]:
        """(chunk rows, the longest row, the room the step has left)."""
        eng = self.eng
        cfg = eng.cfg
        # What one dispatch carries is held to the step's width
        # (Engine.step_tokens: a packed program has no room beyond it):
        # the decode lanes first, the lookahead lanes the scheduler did
        # not count among them, then the chunks, then forced runs from
        # what is left. A chunk or a run cut short costs its row a later
        # step and changes no token.
        room = eng.step_tokens - n_decode
        chunk_info: list[tuple[int, Any, int, int]] = []
        smax = 1
        for sid, want in prefill_chunks.items():
            seq = eng.sequences.get(sid)
            if seq is None or sid not in eng._prefilling:
                continue
            done = eng._prefilling[sid]
            c = eng.alloc.clamp_chunk(sid, done, seq.prompt_len, min(
                want, cfg.mixed_buckets[-1], seq.prompt_len - done, room))
            if c <= 0:
                continue
            chunk_info.append((sid, seq, done, c))
            smax = max(smax, c)
            room -= c
        B = cfg.max_batch_size
        if n_decode + len(chunk_info) > B:
            raise ValueError(
                f"async mixed batch of {n_decode} decode + "
                f"{len(chunk_info)} prefill rows exceeds max_batch_size={B}"
            )
        return chunk_info, smax, room

    def _ffwd_plans(self, decode: list, room: int) -> tuple[dict, int]:
        """The forced runs this dispatch appends, and the room left."""
        eng = self.eng
        cfg = eng.cfg
        # Grammar fast-forward plans: a constrained decode row whose
        # current FSM state forces a run of singleton-mask tokens appends
        # the whole run in THIS dispatch (the q_len>1 path chunk rows
        # already ride) and samples only the token after it — every
        # forced token skips a forward pass. Host state must pin the
        # row's FSM state, so rows with more than one uncommitted token
        # wait for commits to catch up (after a fast-forward tick the
        # row rides the normal carry for a tick, then re-engages). A
        # CONTINUING row's one in-flight token was sampled at the forced
        # state, so it IS run[0] by determinism: feed it from host
        # knowledge instead of the carry and append the rest.
        # plan: seq_id -> (anchor token, pre-accept tokens, device ov state)
        ffwd_plan: dict[int, tuple[int, list[int], int]] = {}
        if getattr(cfg, "grammar_ffwd", False):
            for s in decode:
                sid = s.seq_id
                inflight = self._inflight_toks.get(sid, 0)
                if inflight > 1:
                    continue
                fsm = device_table_fsm(s.mask_fn)
                if fsm is None:
                    continue
                st0 = s.mask_fn.dfa_state(s.tokens)
                run = fsm.forced_run(st0) if st0 >= 0 else []
                if run and run[-1] == fsm.eos_id:
                    # A masked sample at the eos-only state yields eos at
                    # any temperature — the trailing eos needs no append.
                    run = run[:-1]
                if inflight == 1:
                    if (
                        sid not in self._prev_lane
                        or sid not in self._prev_emitted
                        or len(run) < 2
                    ):
                        continue
                    anchor, pre = run[0], run[1:]
                else:
                    if not run:
                        continue
                    anchor = (
                        s.tokens[-1] if s.tokens else eng.tokenizer.bos_id
                    )
                    pre = run
                # Never overshoot max_tokens (pre + one sampled token on
                # top of what is already in flight), the largest bucket,
                # or what the step still carries.
                cap = min(
                    s.params.max_tokens - len(s.tokens) - inflight - 1,
                    cfg.mixed_buckets[-1] - 1,
                    room,
                )
                pre = pre[: max(0, cap)]
                if not pre:
                    continue
                # The masked sample applies the state AFTER everything
                # this dispatch consumes beyond st0: the anchor too for
                # a continuing row (its in-flight token was sampled AT
                # st0), just the pre tokens for a settled one (st0
                # already includes the anchor = last host token).
                st = st0
                for t in ([anchor] + pre) if inflight == 1 else pre:
                    st = fsm.advance(st, t)
                # Book the extra tokens; a dry pool drops the plan (the
                # row keeps its normal one-token path).
                try:
                    eng.alloc.extend(sid, len(pre))
                except OutOfPages:
                    eng.alloc.truncate(sid, eng.alloc.length(sid))
                    continue
                ffwd_plan[sid] = (int(anchor), pre, st + 1)
                room -= len(pre)
        return ffwd_plan, room

    def _assign_lanes(
        self, decode: list, chunk_info: list
    ) -> tuple[dict[int, int], set[int]]:
        B = self.eng.cfg.max_batch_size
        # Lane assignment: continuing decode rows keep their lane (the
        # carry is indexed by lane); everyone else takes a free one.
        taken: set[int] = set()
        lane_of: dict[int, int] = {}
        continuing: set[int] = set()
        for s in decode:
            ln = self._prev_lane.get(s.seq_id)
            if (
                ln is not None and s.seq_id in self._prev_emitted
                and ln not in taken
            ):
                lane_of[s.seq_id] = ln
                taken.add(ln)
                continuing.add(s.seq_id)
        for sid, *_ in chunk_info:
            ln = self._prev_lane.get(sid)
            if ln is not None and ln not in taken:
                lane_of[sid] = ln
                taken.add(ln)
        free = [i for i in range(B) if i not in taken]
        for s in decode:
            if s.seq_id not in lane_of:
                lane_of[s.seq_id] = free.pop(0)
        for sid, *_ in chunk_info:
            if sid not in lane_of:
                lane_of[sid] = free.pop(0)
        return lane_of, continuing

    def _arrays(
        self, S: int, decode: list, chunk_info: list, ffwd_plan: dict,
        lane_of: dict[int, int], continuing: set[int],
    ) -> tuple[dict[str, np.ndarray], Any, list, list]:
        """The step program's host arguments by name, the FSM whose tables
        it takes, and the rows seated. The allocator's share (a
        ``_pass_row`` a row) is summed into ``pages`` by the engine."""
        eng = self.eng
        B = eng.cfg.max_batch_size
        MaxP = eng.alloc.table_width
        tokens = np.full((B, S), eng.tokenizer.pad_id, np.int32)
        use_carry = np.zeros((B,), bool)
        starts = np.zeros((B,), np.int32)
        qlens = np.zeros((B,), np.int32)
        emits = np.zeros((B,), bool)
        ov_fsm = np.zeros((B,), np.int32)
        tables = np.full((B, MaxP), -1, np.int32)
        temps = np.zeros((B,), np.float32)
        top_k = np.zeros((B,), np.int32)
        top_p = np.ones((B,), np.float32)
        fsm_obj = None

        def _seat_fsm(fsm):
            nonlocal fsm_obj
            if fsm_obj is None:
                fsm_obj = fsm
            elif fsm_obj is not fsm:
                # The scheduler routes mixed-schema ticks to the sync
                # lane; reaching here means a caller bypassed that gate.
                raise ValueError(
                    "distinct FSM schemas in one async mixed dispatch"
                )

        def _walk(fsm, toks: list[int]) -> int:
            st = fsm.dfa.start
            for t in toks:
                if t != fsm.eos_id:
                    st = fsm.advance(st, t)
            return st + 1  # device-table row 0 is the FREE sentinel

        dec_rows: list[tuple[Any, int]] = []
        for s in decode:
            lane = lane_of[s.seq_id]
            dec_rows.append((s, lane))
            plan = ffwd_plan.get(s.seq_id)
            q = 1 if plan is None else 1 + len(plan[1])
            qlens[lane] = q
            emits[lane] = True
            # The bookings above made alloc.length = written + inflight
            # + q; the row writes its q inputs from the slots before it.
            starts[lane] = eng.alloc.length(s.seq_id) - q
            tables[lane] = eng._pass_row(s.seq_id, int(starts[lane]), q)
            temps[lane] = s.params.temperature
            top_k[lane] = s.params.top_k
            top_p[lane] = s.params.top_p
            fsm = device_table_fsm(s.mask_fn)
            if fsm is not None:
                _seat_fsm(fsm)
            if plan is not None:
                # Fast-forward row: every input token is host-known (the
                # anchor by forced determinism), so the carry is unused.
                anchor, pre, ov_dev = plan
                tokens[lane, :q] = [anchor] + pre
                ov_fsm[lane] = ov_dev
            elif s.seq_id in continuing:
                use_carry[lane] = True
            else:
                tokens[lane, 0] = (
                    s.tokens[-1] if s.tokens else eng.tokenizer.bos_id
                )
                if fsm is not None:
                    ov_fsm[lane] = _walk(fsm, s.tokens)
        chk_rows: list[tuple[int, int, int, int, bool]] = []
        for sid, seq, done, c in chunk_info:
            lane = lane_of[sid]
            finishing = done + c >= seq.prompt_len
            chk_rows.append((sid, lane, done, c, finishing))
            tokens[lane, :c] = seq.prompt_ids[done:done + c]
            starts[lane] = done
            qlens[lane] = c
            tables[lane] = eng._pass_row(sid, done, c)
            temps[lane] = seq.params.temperature
            top_k[lane] = seq.params.top_k
            top_p[lane] = seq.params.top_p
            emits[lane] = finishing
            if finishing:
                fsm = device_table_fsm(seq.mask_fn)
                if fsm is not None:
                    _seat_fsm(fsm)
                    ov_fsm[lane] = _walk(fsm, seq.tokens)
        host = dict(
            tokens=tokens, use_carry=use_carry, starts=starts, qlens=qlens,
            emits=emits, tables=tables, temps=temps, top_k=top_k,
            top_p=top_p, ov_fsm=ov_fsm,
        )
        return host, fsm_obj, dec_rows, chk_rows

    def _enqueue(
        self, S: int, host: dict[str, np.ndarray], fsm_obj, dec_rows: list,
        chk_rows: list, ffwd_plan: dict,
    ) -> bool:
        """Place the arrays, call the step program, account and book."""
        eng = self.eng
        B = eng.cfg.max_batch_size
        starts, qlens = host["starts"], host["qlens"]
        with obs.phase("plan", part="account"):
            width = eng._count_step_tokens(S, int(qlens.sum()))
            eng._count_context(starts + qlens, qlens > 0)
            ticket = eng.step_clock.enqueue(width)
            tick_id, t_disp, _ = ticket
            if eng._mixed_gap_stamp is not None:
                obs.STEP_HOST_GAP_SECONDS.observe(
                    t_disp - eng._mixed_gap_stamp, mode="async"
                )
        try:
            with obs.phase("dispatch", tick=tick_id), eng.mesh_ctx():
                with obs.phase("dispatch", part="place"):
                    eng._sample_key, sub = jax.random.split(eng._sample_key)
                    carry = eng._async_carry
                    if carry is None:
                        carry = jnp.zeros((B,), jnp.int32)
                    fsmc = eng._async_fsm_carry
                    if fsmc is None:
                        fsmc = jnp.zeros((B,), jnp.int32)
                    if fsm_obj is not None:
                        fm, fd = eng._fsm_device_tables(fsm_obj)
                    else:
                        fm = fd = None
                    dev = {k: jnp.asarray(a) for k, a in host.items()}
                with obs.phase("dispatch", part="call"), \
                        annotate("engine.mixed_step_async"):
                    toks_d, eng.cache, fsm_d = eng._mixed_carry_jit(
                        eng.params,
                        dev["tokens"],
                        dev["use_carry"],
                        carry,
                        dev["starts"],
                        dev["qlens"],
                        dev["emits"],
                        eng.cache,
                        dev["tables"],
                        sub,
                        dev["temps"],
                        dev["top_k"],
                        dev["top_p"],
                        fsm_mask=fm,
                        fsm_dest=fd,
                        carry_fsm=fsmc,
                        ov_fsm=dev["ov_fsm"],
                    )
            eng._async_carry = toks_d
            eng._async_fsm_carry = fsm_d
        except Exception:
            # Salvage what earlier (healthy) dispatches produced, then
            # roll back THIS tick: the +1 bookings are for tokens the
            # failed dispatch never wrote, and its chunk admissions
            # follow step_mixed's drop-and-reraise contract.
            try:
                self.flush()
            except Exception:  # noqa: BLE001 - device may be gone
                log.exception("async pipeline salvage flush failed")
            for s, _lane in dec_rows:
                if not s.done and s.seq_id in eng.sequences:
                    plan = ffwd_plan.get(s.seq_id)
                    booked = 1 if plan is None else 1 + len(plan[1])
                    eng.alloc.truncate(
                        s.seq_id, eng.alloc.length(s.seq_id) - booked
                    )
            for sid, *_ in chk_rows:
                eng._drop_admission(sid)
            self._prev_lane = {}
            self._prev_emitted = set()
            raise
        eng._mixed_gap_stamp = time.perf_counter()
        with obs.phase("plan", part="account"):
            self._account_dispatch(
                S, starts, qlens, dec_rows, chk_rows, ffwd_plan, tick_id)
        with obs.phase("plan", part="book"):
            # Book-keeping AFTER the dispatch succeeded: planned prefill
            # progress advances (the write is enqueued — deterministic),
            # the finishing set gains this tick's completing prompts, and
            # every emitting row carries one more uncommitted token.
            for sid, _lane, done, c, finishing in chk_rows:
                eng._prefilling[sid] = done + c
                if finishing:
                    self._finishing.add(sid)
                    self._inflight_toks[sid] = (
                        self._inflight_toks.get(sid, 0) + 1
                    )
            self._prev_lane = {}
            self._prev_emitted = set()
            for s, lane in dec_rows:
                self._prev_lane[s.seq_id] = lane
                self._prev_emitted.add(s.seq_id)
                plan = ffwd_plan.get(s.seq_id)
                self._inflight_toks[s.seq_id] = (
                    self._inflight_toks.get(s.seq_id, 0) + 1
                    + (0 if plan is None else len(plan[1]))
                )
            for sid, lane, _done, _c, finishing in chk_rows:
                self._prev_lane[sid] = lane
                if finishing:
                    self._prev_emitted.add(sid)
            self._pending.append(_Tick(
                toks_d=toks_d,
                decode=[(s.seq_id, lane) for s, lane in dec_rows],
                chunks=chk_rows,
                ffwd={sid: plan[1] for sid, plan in ffwd_plan.items()},
                ticket=ticket,
                bucket=int(S),
            ))
        return True

    def _account_dispatch(
        self, S, starts, qlens, dec_rows, chk_rows, ffwd_plan, tick_id
    ) -> None:
        """What only observes the dispatch just enqueued: the token
        counters, the attribution ledger's composition, the flight event."""
        eng = self.eng
        cfg = eng.cfg
        n_prefill = int(sum(c for _s, _l, _d, c, _f in chk_rows))
        if n_prefill:
            get_perf_stats().record_metric(
                "engine.prefill_tokens", n_prefill, "tok")
            obs.PREFILL_TOKENS.inc(n_prefill)
        from .decode_loop import record_async_dispatch

        from ..obs.attribution import prefill_attn_positions

        # Decode-lane composition sums use each row's true q (1 for a
        # plain lane, 1 + run length for a fast-forward append); with no
        # ffwd rows they reduce to the previous one-token formulas.
        dec_q = int(sum(int(qlens[lane]) for _s, lane in dec_rows))
        dec_ctx = int(sum(
            int(starts[lane]) + int(qlens[lane]) for _s, lane in dec_rows
        ))
        record_async_dispatch(
            decode_rows=len(dec_rows),
            prefill_tokens=n_prefill,
            depth=len(self._pending) + 1,
            attr=getattr(eng, "attr", None),
            attr_kw=dict(
                q_tokens=dec_q + n_prefill,
                kv_read_tokens=dec_ctx + int(sum(
                    d + c for _sid, _l, d, c, _f in chk_rows
                )),
                kv_write_tokens=dec_q + n_prefill,
                attn_q_ctx=int(sum(
                    prefill_attn_positions(
                        int(starts[lane]), int(qlens[lane])
                    )
                    for _s, lane in dec_rows
                )) + int(sum(
                    prefill_attn_positions(d, c)
                    for _sid, _l, d, c, _f in chk_rows
                )),
            ),
        )
        n_forced = int(sum(len(p[1]) for p in ffwd_plan.values()))
        if ffwd_plan:
            from .decode_loop import record_ffwd_append

            for s, _lane in dec_rows:
                plan = ffwd_plan.get(s.seq_id)
                if plan is not None:
                    record_ffwd_append(
                        s.seq_id, len(plan[1]),
                        attr=getattr(eng, "attr", None),
                        request_id=obs.flight.request_id_of(s.trace),
                    )
        obs.flight.record(
            "dispatch", op="mixed",
            decode_seq_ids=[s.seq_id for s, _ in dec_rows],
            prefill_seq_ids=[sid for sid, *_ in chk_rows],
            bucket=int(S), prefill_tokens=n_prefill,
            forced_tokens=n_forced,
            budget=cfg.max_step_tokens,
            tick=tick_id, pipeline_pos=len(self._pending),
        )

    # -- commit phase --------------------------------------------------------
    def _dec_inflight(self, sid: int) -> None:
        left = self._inflight_toks.get(sid, 0) - 1
        if left > 0:
            self._inflight_toks[sid] = left
        else:
            self._inflight_toks.pop(sid, None)

    def _commit_oldest(self) -> None:
        eng = self.eng
        tick = self._pending.popleft()
        # Nothing enqueued behind the step pulled: the device idles from
        # the moment it is ready until the next dispatch (wait's ``alone``).
        overlapped = bool(self._pending)
        sampled = eng._pull(
            "mixed", tick.bucket, tick.ticket, tick.toks_d,
            alone=not overlapped,
        )
        with obs.phase("commit", tick=tick.ticket[0]):
            self._commit(tick, sampled, overlapped)

    def _commit(
        self, tick: _Tick, sampled: np.ndarray, overlapped: bool
    ) -> None:
        """Fold one pulled tick into host state: accept, stop scan,
        detokenize, stream, roll finished rows' bookings back. The parts
        of ``commit``: ``accept`` (with ``stream`` and ``stop_scan`` summed
        out of it by ``Engine._accept_token``) and ``account``."""
        eng = self.eng
        perf = get_perf_stats()
        tick_id, t_disp, _ = tick.ticket
        decode_out, prefill_out = self._results
        produced = 0
        with eng._accepting():
            for sid, lane in tick.decode:
                pre = tick.ffwd.get(sid, [])
                n_toks = 1 + len(pre)
                for _ in range(n_toks):
                    self._dec_inflight(sid)
                s = eng.sequences.get(sid)
                if s is None or s.done:
                    # Stop/EOS detection lagged a tick: this row finished
                    # at an earlier commit (or was dropped) while this
                    # dispatch was in flight. Its tokens are discarded; the
                    # page booking was already rolled back by the done-path
                    # truncate.
                    obs.ASYNC_OVERSHOOT_TOKENS.inc(n_toks)
                    continue
                dspan = s.decode_span
                accepted = 0
                # Fast-forward pre-accepts land first (they precede the
                # sampled token in the append), so the stop-string/EOS scan
                # runs over the run in order and a mid-run stop discards
                # the tail as overshoot.
                for tok in list(pre) + [int(sampled[lane])]:
                    if s.done:
                        obs.ASYNC_OVERSHOOT_TOKENS.inc()
                        continue
                    try:
                        eng._accept_token(s, tok)
                    except Exception:  # noqa: BLE001 - raising stream callback
                        # Row-local isolation without propagation, exactly
                        # like step_mixed: the reap path surfaces "error";
                        # raising here would lose the same tick's other
                        # rows.
                        s.done = True
                        s.finish_reason = s.finish_reason or "error"
                    decode_out.setdefault(sid, []).append(tok)
                    accepted += 1
                produced += accepted
                if dspan is not None:
                    t0 = time.perf_counter()
                    dspan.child(
                        "ffwd_step" if pre else "mixed_step",
                        t_disp, t0, tokens=accepted, tick=tick_id,
                    )
                    eng._summed("account", t0)
                if s.done:
                    # Roll bookings (including any still-in-flight
                    # lookahead tokens') back to written content; later
                    # stale writes land harmlessly before any new owner's
                    # (dispatch order).
                    eng.alloc.truncate(sid, eng._host_written(s))
            for sid, lane, done, c, finishing in tick.chunks:
                seq = eng.sequences.get(sid)
                if seq is None:
                    # Dropped by a failure path while this tick was in
                    # flight.
                    if finishing:
                        self._finishing.discard(sid)
                        self._dec_inflight(sid)
                    continue
                if not finishing:
                    prefill_out[sid] = False
                    continue
                # Finishing chunk: the prompt's first sampled token.
                self._finishing.discard(sid)
                self._dec_inflight(sid)
                eng._prefilling.pop(sid, None)
                token = int(sampled[lane])
                seq.ttft_s = time.perf_counter() - seq.started_s
                perf.record_metric("engine.ttft", seq.ttft_s * 1e3, "ms")
                eng._first_token_obs(seq)
                try:
                    eng._accept_token(seq, token)
                except Exception as e:  # noqa: BLE001 - stream callback
                    eng._drop_admission(sid)
                    prefill_out[sid] = e
                    continue
                prefill_out[sid] = True
                if seq.done:
                    eng.alloc.truncate(sid, eng._host_written(seq))
        with obs.phase("commit", part="account"):
            if produced:
                perf.record_metric("engine.decode_tokens", produced, "tok")
            from .decode_loop import record_async_commit

            record_async_commit(overlapped, len(self._pending))
            eng._observe_occupancy()
