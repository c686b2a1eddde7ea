"""OpenAI-compatible frontend for the serving engine.

Two access paths, one implementation:

- **In-process provider**: registered under the ``tpu://`` scheme with the
  agent's ChatClient (llm/client.py), so ``--model tpu://tiny-test`` routes
  the ReAct loop straight into the engine with zero HTTP hops and zero
  external API calls (BASELINE.json north_star).
- **HTTP server**: ``opsagent serve-engine`` exposes POST
  /v1/chat/completions (non-streaming and SSE streaming), GET /v1/models and
  /healthz for out-of-process clients speaking the unchanged OpenAI wire
  format.
"""

from __future__ import annotations

import asyncio
import json
import queue
import threading
import time
import uuid
from typing import Any

from .. import obs
from ..llm.client import register_provider
from ..utils.jsonrepair import parse_json
from ..utils.logger import get_logger
from . import faults
from .chat_template import apply_chat_template
from .engine import Engine, EngineConfig
from .sampler import SamplingParams
from .scheduler import Request, RequestError, Scheduler

log = get_logger("serving.api")


# What ``ServingStack._stream_events`` yields where it needs the next item of
# its stream's queue: the driver sends the item in.
_NEXT_TOKEN = object()


class _StampedChunk(dict):
    """A streamed content chunk that remembers when the scheduler thread
    handed over the newest token it carries (``time.perf_counter()``): the
    one stamp that travels from ``on_token`` to the HTTP handler, which
    reads it at ``resp.write`` (``opsagent_stream_emit_lag_seconds``). It
    serialises as the plain dict it is."""

    stamp: float = 0.0


class _SpanFinisher:
    """Duck-types ``Trace.finish()`` for a nested fleet-hop leg span:
    the completion paths call ``owned.finish()`` in their finally
    blocks, and a leg span that never closed would extend to "now"
    forever in the assembled timeline."""

    def __init__(self, span: Any):
        self._span = span

    def finish(self) -> None:
        self._span.close()


class ServingStack:
    """Engine + scheduler + chat glue for one hosted model."""

    def __init__(self, engine: Engine, restart_tolerant: bool = True):
        # Slice-restart tolerance: the scheduler rebuilds a fresh engine
        # from the same config if the device runtime fails persistently,
        # re-admitting in-flight work (scheduler._recover). ``engine``
        # is a property so restarts are transparent to every consumer.
        # The resolved model_cfg rides along: an auto-derived (non-preset)
        # architecture must survive the rebuild, or recovery would die in
        # get_config_preset on the checkpoint-dir name.
        factory = (
            lambda cfg=engine.cfg, mc=engine.model_cfg: Engine(
                cfg, model_cfg=mc
            )
        ) if restart_tolerant else None
        self.scheduler = Scheduler(engine, engine_factory=factory)
        self.scheduler.start()
        self.model_name = engine.model_cfg.name

    @property
    def engine(self) -> Engine:
        # Direct assignment (tests wiring a fake engine without a
        # scheduler) takes precedence; otherwise track the scheduler's
        # current engine so restarts are transparent.
        override = getattr(self, "_engine_override", None)
        if override is not None:
            return override
        return self.scheduler.engine

    @engine.setter
    def engine(self, value: Engine) -> None:
        self._engine_override = value

    # -- request translation ------------------------------------------------
    def _translate(
        self, body: dict[str, Any]
    ) -> tuple[SamplingParams, list[int], Any]:
        """Body -> (sampling, prompt_ids, mask_fn); malformed client params
        (e.g. max_tokens="many") become a 400, not a retryable 500."""
        try:
            return (
                self._sampling_from(body),
                self._prompt_ids(body),
                self._constraint_from(body),
            )
        except (ValueError, TypeError, KeyError) as e:
            raise RequestError(f"invalid request: {e}", 400) from e

    def _tool_choice_constraint(self, body: dict[str, Any]):
        """OpenAI ``tool_choice`` -> constrained-decoding mask_fn forcing a
        tool_calls envelope: "required" constrains to a call of ANY listed
        tool, {"type": "function", "function": {"name": N}} to that tool
        specifically (arguments constrained to the tool's parameter schema
        when it fits the FSM compiler's subset, any-JSON otherwise). The
        structural guarantee the reference could never have — its remote
        models free-text their calls (reference pkg/workflows/swarm.go)."""
        tc = body.get("tool_choice")
        tools = body.get("tools") or []
        if tc in (None, "auto", "none"):
            return None
        names = [
            t.get("function", {}).get("name")
            for t in tools
            if isinstance(t, dict) and t.get("function", {}).get("name")
        ]
        if not names:
            raise ValueError("tool_choice requires a non-empty tools list")
        args_schema: Any = {}
        if tc == "required":
            name_schema: dict[str, Any] = {"enum": names}
        elif isinstance(tc, dict) and tc.get("type") == "function":
            want = tc.get("function", {}).get("name")
            if want not in names:
                raise ValueError(
                    f"tool_choice names unknown function {want!r}"
                )
            name_schema = {"enum": [want]}
            for t in tools:
                if (
                    isinstance(t, dict)
                    and t.get("function", {}).get("name") == want
                ):
                    args_schema = t["function"].get("parameters") or {}
        else:
            raise ValueError(f"unsupported tool_choice {tc!r}")
        from .constrained import json_constraint

        def envelope(args: Any) -> dict[str, Any]:
            return {
                "type": "object",
                "properties": {
                    "tool_calls": {
                        "type": "array",
                        "minItems": 1,
                        "items": {
                            "type": "object",
                            "properties": {
                                "function": {
                                    "type": "object",
                                    "properties": {
                                        "name": name_schema,
                                        "arguments": args,
                                    },
                                },
                            },
                        },
                    },
                },
            }

        # depth=8: the envelope itself consumes 4 nesting levels (object ->
        # array -> item -> function), so the default depth would compile
        # "arguments" at depth 0 — primitives only, '{' forbidden.
        try:
            return json_constraint(
                self.engine.tokenizer, envelope(args_schema), depth=8
            )
        except ValueError:
            # Tool parameter schema outside the FSM compiler's subset:
            # still force the envelope + name, arguments as any JSON.
            return json_constraint(
                self.engine.tokenizer, envelope({}), depth=8
            )

    def _constraint_from(self, body: dict[str, Any]):
        """OpenAI ``response_format`` -> constrained-decoding mask_fn.
        ``json_object`` constrains to any JSON value; ``json_schema`` to the
        given schema (on-device FSM masking — the engine-side replacement
        for the reference's JSON-repair ladder, pkg/utils/json.go:16)."""
        rf = body.get("response_format")
        tc_mask = self._tool_choice_constraint(body)
        if rf and tc_mask is not None:
            raise ValueError(
                "response_format and a forcing tool_choice cannot be "
                "combined (one constrained-decoding grammar per request)"
            )
        if not rf:
            return tc_mask
        if not isinstance(rf, dict):
            raise ValueError(f"response_format must be an object, got {rf!r}")
        from .constrained import json_constraint

        kind = rf.get("type")
        if kind == "json_object":
            return json_constraint(self.engine.tokenizer, None)
        if kind == "json_schema":
            spec = rf.get("json_schema") or {}
            if not isinstance(spec, dict):
                raise ValueError("response_format.json_schema must be an object")
            if "schema" in spec:
                schema = spec["schema"]
            elif any(k in spec for k in ("type", "properties", "enum", "items")):
                schema = spec  # schema passed bare, not nested under "schema"
            else:
                raise ValueError(
                    "response_format.json_schema carries no schema "
                    '(expected a "schema" member or an inline JSON schema)'
                )
            if not isinstance(schema, dict):
                raise ValueError("json_schema.schema must be an object")
            return json_constraint(self.engine.tokenizer, schema or None)
        raise ValueError(f"unsupported response_format type {kind!r}")

    def _sampling_from(self, body: dict[str, Any]) -> SamplingParams:
        logprobs = bool(body.get("logprobs", False))
        top_lp = int(body.get("top_logprobs", 0) or 0)
        if top_lp and not logprobs:
            raise ValueError("top_logprobs requires logprobs: true")
        if not 0 <= top_lp <= 20:
            raise ValueError("top_logprobs must be in 0..20")
        lb_raw = body.get("logit_bias") or {}
        if not isinstance(lb_raw, dict):
            raise ValueError("logit_bias must be an object of id -> bias")
        logit_bias = []
        for k, v in lb_raw.items():
            b = float(v)
            if not -100.0 <= b <= 100.0:
                raise ValueError("logit_bias values must be in -100..100")
            logit_bias.append((int(k), b))
        pres = float(body.get("presence_penalty", 0.0) or 0.0)
        freq = float(body.get("frequency_penalty", 0.0) or 0.0)
        if not -2.0 <= pres <= 2.0 or not -2.0 <= freq <= 2.0:
            raise ValueError("penalties must be in -2..2")
        return SamplingParams(
            temperature=float(body.get("temperature", 0.0) or 0.0),
            top_k=int(body.get("top_k", 0) or 0),
            top_p=float(body.get("top_p", 1.0) or 1.0),
            max_tokens=int(
                body.get("max_tokens") or self.engine.cfg.max_new_tokens_default
            ),
            stop=tuple(
                [body["stop"]] if isinstance(body.get("stop"), str)
                else body.get("stop") or []
            ),
            logprobs=logprobs,
            top_logprobs=top_lp,
            logit_bias=tuple(logit_bias),
            presence_penalty=pres,
            frequency_penalty=freq,
        )

    def _prompt_ids(self, body: dict[str, Any]) -> list[int]:
        # tool_choice "none": the model must not see the tools at all.
        tools = (
            None if body.get("tool_choice") == "none" else body.get("tools")
        )
        return apply_chat_template(
            self.engine.tokenizer,
            body.get("messages", []),
            model_family=self.model_name,
            tools=tools,
        )

    def _finalize_text(
        self, tokens: list[int], stop: tuple[str, ...], finish_reason: str = ""
    ) -> tuple[str, str]:
        """(text, finish_reason) with eos/stop-string trimming."""
        eos = self.engine.tokenizer.eos_id
        finish = finish_reason or "length"
        if tokens and tokens[-1] == eos:
            tokens = tokens[:-1]
            finish = "stop"
        text = self.engine.tokenizer.decode(tokens)
        for s in stop:
            idx = text.find(s)
            if idx >= 0:
                text = text[:idx]
                finish = "stop"
        return text, finish

    @staticmethod
    def _parse_tool_calls(text: str) -> list[dict[str, Any]] | None:
        t = text.strip()
        if '"tool_calls"' not in t:
            return None
        try:
            obj = parse_json(t)
        except ValueError:
            return None
        calls = obj.get("tool_calls") if isinstance(obj, dict) else None
        if not isinstance(calls, list) or not calls:
            return None
        out = []
        for i, c in enumerate(calls):
            fn = c.get("function", {}) if isinstance(c, dict) else {}
            args = fn.get("arguments", "")
            if not isinstance(args, str):
                args = json.dumps(args, ensure_ascii=False)
            out.append(
                {
                    "id": c.get("id") or f"call_{i}",
                    "type": "function",
                    "function": {"name": fn.get("name", ""), "arguments": args},
                }
            )
        return out

    # -- chat.completions ---------------------------------------------------
    def _request_trace(
        self, hop: dict[str, Any] | None = None,
    ) -> tuple[Any, "obs.Span | None", str]:
        """Trace context for one chat completion: nest under the caller's
        current span when one is active (the in-process tpu:// path — the
        ReAct loop's ``llm_turn`` span), otherwise root a NEW trace whose
        request ID doubles as the OpenAI completion id, so
        ``GET /api/trace/<completion id>`` finds it. Returns
        (owned_handle_or_None, parent_span, completion_id).

        ``hop`` is the fleet router's hop stamp ({request_id, hop,
        replica}): the incoming journey ID is ADOPTED instead of minting
        a fresh one, so trace spans, flight events, and attribution on
        every participating replica key to one ID. When this process
        already holds a trace under that ID (in-process fleet: a hedge
        probe or mid-stream failover leg landing beside the first leg),
        the new leg nests as a ``fleet_hop`` child of the existing root —
        one span tree per journey, mirroring engine-restart stitching."""
        parent = obs.current_span()
        if parent is not None:
            return None, parent, f"chatcmpl-{uuid.uuid4().hex[:24]}"
        rid = str(hop.get("request_id") or "") if hop else ""
        hop_kind = str(hop.get("hop", "")) if hop else ""
        if rid:
            existing = obs.get_store().get(rid)
            if existing is not None:
                if hop_kind == "failover":
                    existing.anomalous = True
                leg = existing.root.start_child(
                    "fleet_hop",
                    hop=hop_kind,
                    replica=str(hop.get("replica", "")),
                )
                return _SpanFinisher(leg), leg, rid
        t = obs.Trace(rid or obs.new_request_id("chatcmpl"))
        if hop:
            t.root.set(
                hop=hop_kind,
                replica=str(hop.get("replica", "")),
            )
            # A failover leg IS the anomaly: tail-based retention must
            # keep this journey even on a remote replica that never saw
            # the router's local mark.
            if hop_kind == "failover":
                t.anomalous = True
        obs.get_store().add(t)
        return t, t.root, t.request_id

    @staticmethod
    def _stamp_class(parent: "obs.Span | None", body: Any) -> None:
        """Stamp the request's SLO class on its trace. First writer wins:
        the ReAct loop classifies the OUTER request; nested llm-turn
        completions inherit rather than reclassify."""
        t = getattr(parent, "trace", None)
        if t is not None and not getattr(t, "slo_class", ""):
            t.slo_class = obs.slo.classify(body)

    def chat_completion(self, body: dict[str, Any]) -> dict[str, Any]:
        hop = body.pop("fleet_hop", None) if isinstance(body, dict) \
            else None
        owned, parent, cid = self._request_trace(hop)
        self._stamp_class(parent, body)
        try:
            return self._chat_completion_traced(body, parent, cid)
        finally:
            if owned is not None:
                owned.finish()

    def _chat_completion_traced(
        self, body: dict[str, Any], parent: "obs.Span", cid: str
    ) -> dict[str, Any]:
        sampling, prompt_ids, mask_fn = self._translate(body)
        try:
            n = int(body.get("n", 1) or 1)
        except (TypeError, ValueError) as e:
            raise RequestError(f"invalid n: {e}", 400) from e
        if not 1 <= n <= 8:
            raise RequestError("n must be in 1..8", 400)
        t0 = time.time()
        # n choices = n engine requests sharing the prompt: the prefix
        # cache dedups their KV, so extra choices only pay decode. Each
        # request gets its OWN constraint instance — JsonConstraint walks
        # the DFA incrementally per sequence, so sharing one across
        # interleaved rows would cross their grammar states.
        mask_fns = [mask_fn] + [
            self._constraint_from(body) for _ in range(n - 1)
        ]
        spans = [
            parent.start_child("generate", choice=i) if parent is not None
            else None
            for i in range(n)
        ]
        reqs = [
            Request(
                list(prompt_ids), sampling, mask_fn=mask_fns[i],
                trace=spans[i],
            )
            for i in range(n)
        ]
        for r in reqs:
            self.scheduler.submit(r)
        deadline = time.time() + 600
        try:
            for r in reqs:
                if not r.done.wait(max(0.0, deadline - time.time())):
                    raise TimeoutError("generation timed out")
        finally:
            for i, s in enumerate(spans):
                if s is not None:
                    s.close(tokens=len(reqs[i].tokens))
        errs = [r for r in reqs if r.error]
        if errs:
            raise RequestError(errs[0].error, errs[0].error_status)
        with obs.span("detokenize", parent=parent):
            choices = [
                self._build_choice(i, r, sampling) for i, r in enumerate(reqs)
            ]
        total_completion = sum(len(r.tokens) for r in reqs)
        return {
            "id": cid,
            "object": "chat.completion",
            "created": int(t0),
            "model": body.get("model") or self.model_name,
            "choices": choices,
            "usage": {
                "prompt_tokens": len(prompt_ids),
                "completion_tokens": total_completion,
                "total_tokens": len(prompt_ids) + total_completion,
            },
        }

    def _build_choice(
        self, index: int, req: Request, sampling: SamplingParams
    ) -> dict[str, Any]:
        tokens = req.tokens
        text, finish = self._finalize_text(tokens, sampling.stop, req.finish_reason)
        tool_calls = self._parse_tool_calls(text)
        message: dict[str, Any] = {"role": "assistant", "content": text}
        if tool_calls:
            message = {"role": "assistant", "content": None, "tool_calls": tool_calls}
            finish = "tool_calls"
        choice: dict[str, Any] = {
            "index": index, "message": message, "finish_reason": finish,
        }
        if sampling.logprobs:
            tok = self.engine.tokenizer
            lp_toks = (
                tokens[:-1] if tokens and tokens[-1] == tok.eos_id else tokens
            )
            if finish == "stop" and sampling.stop:
                # logprobs.content must align with the (stop-truncated)
                # message content: _finalize_text cuts the text at the
                # START of the stop match, so keep only tokens whose
                # cumulative decode fits before that index. A token the
                # cut splits mid-way is dropped (conservative: logprobs
                # are a subset of content, never beyond it).
                full = tok.decode(lp_toks)
                hits = [full.find(s) for s in sampling.stop]
                hits = [h for h in hits if h >= 0]
                if hits:
                    cut = min(hits)
                    keep = 0
                    for n in range(1, len(lp_toks) + 1):
                        if len(tok.decode(lp_toks[:n])) <= cut:
                            keep = n
                        else:
                            break
                    lp_toks = lp_toks[:keep]
            choice["logprobs"] = {
                "content": [
                    {
                        # token_str, not decode([t]): decode skips special
                        # tokens (eos would render "") and mangles tokens
                        # that are half of a multi-byte character.
                        "token": tok.token_str(t),
                        "logprob": d["logprob"],
                        "top_logprobs": [
                            {"token": tok.token_str(i), "logprob": l}
                            for i, l in d["top"]
                        ],
                    }
                    for t, d in zip(lp_toks, req.logprob_data)
                ]
            }
        return choice

    def chat_completion_stream(self, body: dict[str, Any]):
        """Generator of SSE chunk dicts (sync; drive from a thread)."""
        # (token, when the scheduler thread handed it over) or None (end).
        token_q: "queue.Queue[tuple[int, float] | None]" = queue.Queue()
        events, close = self._open_stream(body, token_q.put)
        try:
            out = next(events)
            while True:
                if out is _NEXT_TOKEN:
                    out = events.send(token_q.get())
                else:
                    yield out
                    out = next(events)
        except StopIteration:
            return
        finally:
            close()

    async def chat_completion_astream(self, body: dict[str, Any]):
        """The same chunks as an async generator, for a handler on an event
        loop: the scheduler thread hands each token to the loop itself
        (``call_soon_threadsafe``) and the chunk is made there, so a stream
        holds no worker of the loop's pool between tokens and a token costs
        no executor job: the pool has some 17 workers, an engine of 64 rows
        and its queue 96 streams. Only the request's translation, once a
        stream, runs in a worker."""
        loop = asyncio.get_running_loop()
        token_q: "asyncio.Queue[tuple[int, float] | None]" = asyncio.Queue()

        def put(item) -> None:
            try:
                loop.call_soon_threadsafe(token_q.put_nowait, item)
            except RuntimeError:
                pass    # the loop closed under a stream still open

        events, close = await loop.run_in_executor(
            None, self._open_stream, body, put)
        try:
            out = next(events)
            while True:
                if out is _NEXT_TOKEN:
                    out = events.send(await token_q.get())
                else:
                    yield out
                    out = next(events)
        except StopIteration:
            return
        finally:
            close()

    def _open_stream(self, body: dict[str, Any], put):
        """Translate and submit one streamed request whose tokens the
        scheduler thread hands to ``put``. Returns the stream's events
        (``_stream_events``) and what closes its trace."""
        hop = body.pop("fleet_hop", None) if isinstance(body, dict) \
            else None
        sampling, prompt_ids, mask_fn = self._translate(body)
        if sampling.logprobs:
            # Refuse rather than silently dropping the field (and paying
            # the engine's host-stepped logprob path for nothing).
            raise RequestError(
                "logprobs are not supported with stream: true", 400
            )
        try:
            n = int(body.get("n", 1) or 1)
        except (TypeError, ValueError) as e:
            raise RequestError(f"invalid n: {e}", 400) from e
        if n != 1:
            raise RequestError("n > 1 is not supported with stream", 400)
        owned, parent, cid = self._request_trace(hop)
        self._stamp_class(parent, body)
        gen_span = (
            parent.start_child("generate", stream=True)
            if parent is not None else None
        )
        req = Request(
            prompt_ids, sampling, mask_fn=mask_fn,
            on_token=lambda t: put((t, time.perf_counter())),
            trace=gen_span,
        )
        self.scheduler.submit(req)
        created = int(time.time())
        model = body.get("model") or self.model_name
        eos = self.engine.tokenizer.eos_id
        sent: list[int] = []

        def chunk(delta: dict[str, Any], finish: str | None = None):
            return {
                "id": cid,
                "object": "chat.completion.chunk",
                "created": created,
                "model": model,
                "choices": [
                    {"index": 0, "delta": delta, "finish_reason": finish}
                ],
            }

        def close() -> None:
            # Close the trace no matter how the stream ends (a client's
            # disconnect closes the driving generator): the span tree stays
            # retrievable at /api/trace/{cid} with whatever phases ran.
            if gen_span is not None:
                gen_span.close(tokens=len(sent))
            if owned is not None:
                owned.finish()

        threading.Thread(
            target=lambda: (req.done.wait(600), put(None)), daemon=True
        ).start()
        return self._stream_events(req, chunk, sampling, eos, sent), close

    def _stream_events(self, req, chunk, sampling, eos, sent):
        """One stream's chunks, whoever waits for its tokens: yields
        ``_NEXT_TOKEN`` where it needs the next item of the stream's queue,
        which the driver sends in, and a chunk dict otherwise."""
        # Hold the first SSE chunk until the admission outcome is known:
        # admission failures (prompt too long, engine saturated) must surface
        # as an HTTP error status, not a 200 followed by an in-stream error.
        item = yield _NEXT_TOKEN
        if item is None and req.error:
            raise RequestError(req.error, req.error_status)
        yield chunk({"role": "assistant", "content": ""})
        handed = 0.0    # when the newest token read so far was handed over

        def content(text: str) -> _StampedChunk:
            out = _StampedChunk(chunk({"content": text}))
            out.stamp = handed
            return out
        # Incremental detokenization with a SLIDING window (vLLM-style):
        # decode only tokens[prefix_off:] and diff against the same window's
        # previous decode, so per-token cost is O(window), not O(total).
        # Withhold a trailing "�" (incomplete multi-byte char) and hold back
        # max_stop-1 chars so a stop string straddling a chunk boundary is
        # still caught before emission.
        decode = self.engine.tokenizer.decode
        max_stop = max((len(s) for s in sampling.stop), default=0)
        prefix_off = 0   # window start
        read_off = 0     # tokens already diffed within the window
        pending = ""     # decoded but unemitted (stop-string holdback)
        stopped = False

        def feed(tok: int) -> str:
            """The text to emit now that ``tok`` has come ("": none yet)."""
            nonlocal prefix_off, read_off, pending, stopped
            if tok == eos or stopped:
                return ""
            sent.append(tok)
            prefix_text = decode(sent[prefix_off:read_off])
            window_text = decode(sent[prefix_off:])
            if window_text.endswith("�"):
                return ""  # incomplete multi-byte tail; wait for more tokens
            # Both decodes start at prefix_off, so context-dependent effects
            # at the window start (sentencepiece leading-space stripping)
            # cancel in the diff and the windows telescope correctly. Guard
            # against decoders whose cleanup makes prefix_text not a literal
            # prefix of window_text by cutting at the common prefix instead
            # of blindly at len(prefix_text).
            cut = len(prefix_text)
            if window_text[:cut] != prefix_text:
                cut = 0
                for a, b in zip(prefix_text, window_text):
                    if a != b:
                        break
                    cut += 1
            delta = window_text[cut:]
            prefix_off, read_off = read_off, len(sent)
            if not delta:
                return ""
            pending += delta
            for s in sampling.stop:
                idx = pending.find(s)
                if idx >= 0:
                    pending = pending[:idx]
                    stopped = True
                    break
            if stopped:
                emit, pending = pending, ""
            elif max_stop > 1:
                emit, pending = pending[: -(max_stop - 1)], pending[-(max_stop - 1):]
            else:
                emit, pending = pending, ""
            return emit

        while item is not None:
            tok, handed = item
            emit = feed(tok)
            if emit:
                yield content(emit)
            item = yield _NEXT_TOKEN
        if req.error:
            yield {"error": {"message": req.error}}
            return
        if not stopped and pending:
            yield content(pending)
        finish = "stop" if stopped else (req.finish_reason or "length")
        yield chunk({}, finish=finish)

    # -- hierarchical KV tier -----------------------------------------------
    def park(self, messages: list[dict[str, Any]], tools: Any = None) -> int:
        """Tool-time parking (hierarchical KV tier): tokenize the session
        history exactly the way admission would and park its KV chain —
        copy the trie-resident pages to the host pool, free the HBM. The
        agent loop calls this when it enters tool execution: for the
        seconds a ``kubectl``/``trivy``/``python`` subprocess runs, the
        session's pages would otherwise only deny admission to queued
        prompts. The next turn's admission restores the chain with a page
        copy instead of re-prefilling it. Returns tokens parked (0 when
        the offload tier is off — the call is always safe)."""
        eng = self.engine
        if getattr(eng, "offload", None) is None:
            return 0
        try:
            ids = apply_chat_template(
                eng.tokenizer, messages or [],
                model_family=self.model_name, tools=tools,
            )
        except Exception:  # noqa: BLE001 - parking is best-effort
            return 0
        return eng.park_chain(ids)

    # -- lifecycle ----------------------------------------------------------
    def close(self) -> None:
        self.scheduler.stop()


# -- in-process tpu:// provider ---------------------------------------------
_stacks: dict[str, ServingStack] = {}
_stacks_lock = threading.Lock()


def install_stack(name: str, stack: ServingStack) -> None:
    """Register an engine under a tpu:// model name (tests, co-hosting)."""
    with _stacks_lock:
        _stacks[name] = stack


def uninstall_stack(name: str) -> None:
    """Remove a registered stack (the caller closes it)."""
    with _stacks_lock:
        _stacks.pop(name, None)


def installed_stack_max_position(name: str) -> int | None:
    """Context window of an ALREADY-installed stack, or None.

    Unlike get_stack this never constructs an engine: it exists so the
    agent-side token constrictor (llm/tokens.py) can budget against the
    exact window the engine enforces at admission (engine.add_request
    rejects prompts >= max_position) for stacks installed under arbitrary
    names like tpu://real or tpu://tiny-agent, without triggering a
    device-resident engine build on a lookup."""
    with _stacks_lock:
        stack = _stacks.get(name)
        if stack is None:
            # Case-insensitive rescue: tokens.py lowercases model names,
            # but install_stack keeps the caller's case.
            low = name.lower()
            stack = next(
                (s for k, s in _stacks.items() if k.lower() == low), None
            )
    if stack is None:
        return None
    return int(stack.engine.model_cfg.max_position)


def park_session(model: str, messages: list[dict[str, Any]],
                 tools: Any = None) -> int:
    """Tool-exec signal from the agent loop: park the session's KV to the
    host tier while the tool subprocess runs (see ServingStack.park).
    ``model`` may carry the tpu:// scheme. Looks up the ALREADY-installed
    stack only — never constructs an engine — and never raises: parking
    is an optimization, the loop must survive its absence."""
    name = model.split("://", 1)[-1]
    with _stacks_lock:
        stack = _stacks.get(name)
        if stack is None:
            low = name.lower()
            stack = next(
                (s for k, s in _stacks.items() if k.lower() == low), None
            )
    if stack is None:
        return 0
    try:
        return stack.park(messages, tools=tools)
    except Exception:  # noqa: BLE001
        log.exception("tool-time parking failed (ignored)")
        return 0


def get_stack(name: str) -> ServingStack:
    # Engine construction happens under the lock: two racing first requests
    # must not each build a device-resident engine (the loser would leak
    # device memory and a scheduler thread).
    with _stacks_lock:
        if name not in _stacks:
            log.info("creating in-process engine for tpu://%s", name)
            _stacks[name] = ServingStack(Engine(EngineConfig(model=name)))
        return _stacks[name]


def _tpu_provider_factory(target: str):
    from ..llm.client import LLMError

    def provider(body: dict[str, Any]) -> dict[str, Any]:
        stack = get_stack(target or body.get("model", ""))
        try:
            return stack.chat_completion(body)
        except Exception as e:  # noqa: BLE001 - agent loop handles LLMError
            raise LLMError(f"tpu engine error: {e}") from e

    return provider


register_provider("tpu", _tpu_provider_factory)


# -- HTTP server -------------------------------------------------------------
def build_engine_app(stack: ServingStack, membership=None):
    """``membership`` is the replica's fleet membership (serve-engine
    --join-fleet; serving/fleet/client.py): it feeds the /healthz
    ``fleet`` block and the /fleet/drain notification state."""
    from aiohttp import web

    async def models(request: web.Request) -> web.Response:
        return web.json_response(
            {
                "object": "list",
                "data": [
                    {
                        "id": stack.model_name,
                        "object": "model",
                        "owned_by": "opsagent-tpu",
                    }
                ],
            }
        )

    async def healthz(request: web.Request) -> web.Response:
        eng = stack.engine
        sched = getattr(stack, "scheduler", None)
        body = {
            "status": "ok",
            "model": stack.model_name,
            "free_pages": eng.alloc.free_pages,
            "running": len(eng.sequences),
            # Scheduler queue depth: the fleet router's spill-over input.
            "queued": len(getattr(sched, "_waiting", ()))
            + (sched._queue.qsize() if hasattr(sched, "_queue") else 0),
            "prefilling": len(getattr(sched, "_prefilling", ())),
            "prefix_hit_tokens": eng.alloc.hit_tokens,
            "prefix_miss_tokens": eng.alloc.miss_tokens,
            "prefix_evictions": eng.alloc.evictions,
            # What actually runs and on what (device, mesh, attn impl,
            # weight + KV quant, FSM tables): fleet snapshots and sweep
            # readers self-describe instead of inferring it from env.
            "impl": eng.impl_info(),
            "device_memory": eng.device_memory(),
        }
        if getattr(eng, "init_stats", None):
            # Cold-start provenance: how long weights + warmup took, and
            # whether this engine came up fresh or from a snapshot.
            body["init"] = dict(eng.init_stats)
        if getattr(eng, "offload", None) is not None:
            body["host_pool"] = eng.offload.stats()
        if getattr(eng.cfg, "async_depth", 1) > 1:
            body["async"] = {
                "depth": eng.cfg.async_depth,
                "inflight": eng.async_pending(),
            }
        if membership is not None:
            body["fleet"] = membership.healthz_block()
        if faults.active():
            # Chaos visibility: which fault points are armed and what has
            # fired — so an operator can tell injected pain from real pain.
            body["faults"] = faults.summary()
        return web.json_response(body)

    async def completions(request: web.Request) -> web.StreamResponse:
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response(
                {"error": {"message": "invalid JSON body"}}, status=400
            )
        if not body.get("messages"):
            return web.json_response(
                {"error": {"message": "messages is required"}}, status=400
            )
        # Fleet hop annotation: the router stamps the journey both in
        # the body (primary carrier) and as X-Fleet-* headers; accept
        # the headers so a front proxy that strips unknown body fields
        # still propagates the journey ID to this replica's spans.
        if "fleet_hop" not in body:
            hdr_rid = request.headers.get("X-Fleet-Request-Id")
            if hdr_rid:
                body["fleet_hop"] = {
                    "request_id": hdr_rid,
                    "hop": request.headers.get("X-Fleet-Hop", ""),
                    "replica": request.headers.get(
                        "X-Fleet-Replica", ""
                    ),
                }
        loop = asyncio.get_running_loop()
        if body.get("stream"):
            gen = stack.chat_completion_astream(body)
            # Pull the first chunk BEFORE preparing the stream: request-
            # translation errors (bad sampling params, prompt too long)
            # surface as a proper JSON error status, not a dead connection.
            try:
                first = await anext(gen, None)
            except Exception as e:  # noqa: BLE001
                status = e.status if isinstance(e, RequestError) else 500
                return web.json_response(
                    {"error": {"message": str(e), "type": type(e).__name__}},
                    status=status,
                )
            resp = web.StreamResponse(
                headers={
                    "Content-Type": "text/event-stream",
                    "Cache-Control": "no-cache",
                }
            )
            await resp.prepare(request)
            chunk = first
            try:
                while chunk is not None:
                    if isinstance(chunk, _StampedChunk):
                        obs.STREAM_EMIT_LAG_SECONDS.observe(
                            time.perf_counter() - chunk.stamp
                        )
                    await resp.write(
                        b"data: " + json.dumps(chunk).encode("utf-8") + b"\n\n"
                    )
                    chunk = await anext(gen, None)
            except Exception as e:  # noqa: BLE001 - headers already sent
                log.exception("stream failed mid-flight")
                err = {"error": {"message": str(e), "type": type(e).__name__}}
                await resp.write(
                    b"data: " + json.dumps(err).encode("utf-8") + b"\n\n"
                )
            finally:
                await gen.aclose()    # the stream's trace closes now
            await resp.write(b"data: [DONE]\n\n")
            await resp.write_eof()
            return resp
        try:
            out = await loop.run_in_executor(None, stack.chat_completion, body)
        except Exception as e:  # noqa: BLE001 - OpenAI-style error envelope
            status = e.status if isinstance(e, RequestError) else 500
            return web.json_response(
                {"error": {"message": str(e), "type": type(e).__name__}},
                status=status,
            )
        return web.json_response(out)

    async def profile_start(request: web.Request) -> web.Response:
        # On-demand jax.profiler capture around live traffic: POST (body
        # ignored), then hit /v1/profile/stop and open the configured
        # --profile-dir in TensorBoard. The device-side complement to
        # GET /api/perf/stats' host timers (reference only has the
        # latter: pkg/api/router.go:104).
        from ..utils.profiling import profile_dir

        # The trace destination is operator-configured only (--profile-dir
        # / $OPSAGENT_PROFILE_DIR): a network client must not get an
        # arbitrary-filesystem-write primitive out of the serving port.
        logdir = profile_dir()
        if not logdir:
            return web.json_response(
                {"error": {"message": "profiling not enabled: start the "
                                      "server with --profile-dir"}},
                status=403,
            )
        import jax

        try:
            jax.profiler.start_trace(logdir)
        except Exception as e:  # noqa: BLE001 - already tracing / bad dir
            return web.json_response(
                {"error": {"message": str(e)}}, status=409
            )
        return web.json_response({"status": "tracing", "logdir": logdir})

    async def profile_stop(request: web.Request) -> web.Response:
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 - not tracing / write failure
            return web.json_response(
                {"error": {"message": str(e)}}, status=409
            )
        return web.json_response({"status": "stopped"})

    async def metrics(request: web.Request) -> web.Response:
        # Freshen the engine gauges at scrape time: an idle engine's last
        # step may be minutes old, but page residency (held sessions,
        # prefix-cache content) changes meanwhile.
        eng = stack.engine
        try:
            with eng.lock:
                eng._observe_occupancy()
            eng.sync_device_counters()
        except AttributeError:
            pass  # test fakes without the full engine surface
        return web.Response(
            text=obs.metrics_text(),
            content_type="text/plain",
            charset="utf-8",
            headers={"X-Prometheus-Version": "0.0.4"},
        )

    async def trace_get(request: web.Request) -> web.Response:
        t = obs.get_trace(request.match_info["request_id"])
        if t is None:
            return web.json_response(
                {"error": {"message": "unknown request_id"}}, status=404
            )
        return web.json_response(t)

    async def timeline_get(request: web.Request) -> web.Response:
        # The request's assembled lifecycle timeline (trace spans +
        # flight events): non-overlapping phase segments, the goodput
        # split, and the attributable flight events — works mid-flight
        # and across engine restarts (obs/timeline.py).
        tl = obs.timeline.assemble(request.match_info["request_id"])
        if tl is None:
            return web.json_response(
                {"error": {"message": "unknown request_id"}}, status=404
            )
        return web.json_response(tl)

    async def memory_profile(request: web.Request) -> web.Response:
        # GET /api/debug/memory — dump the device memory profile (pprof)
        # into the operator-configured profile dir: live HBM page
        # pressure, visible without waiting for a crash. Same
        # operator-dir-only guard as /api/debug/profile: a network
        # client must not pick the write path.
        import os as _os

        from ..utils.profiling import profile_dir, save_device_memory_profile

        logdir = profile_dir()
        if not logdir:
            return web.json_response(
                {"error": {"message": "profiling not enabled: start the "
                                      "server with --profile-dir"}},
                status=403,
            )
        stamp = time.strftime("%Y%m%dT%H%M%S", time.gmtime())
        path = _os.path.join(logdir, f"memory-{stamp}.prof")
        loop = asyncio.get_running_loop()
        try:
            _os.makedirs(logdir, exist_ok=True)
            await loop.run_in_executor(
                None, save_device_memory_profile, path
            )
        except Exception as e:  # noqa: BLE001 - surfaced to the caller
            return web.json_response(
                {"error": {"message": str(e)}}, status=500
            )
        return web.json_response({"status": "saved", "path": path})

    async def flight_get(request: web.Request) -> web.Response:
        # The flight recorder's event ring: what the engine/scheduler
        # actually did, newest last. ?n= caps the event count, ?kind=
        # filters (admission/dispatch/compile/anomaly/...).
        try:
            n = int(request.query["n"]) if "n" in request.query else None
        except ValueError:
            return web.json_response(
                {"error": {"message": "n must be an integer"}}, status=400
            )
        rec = obs.flight.get_recorder()
        return web.json_response({
            **rec.stats(),
            "events": rec.snapshot(n=n, kind=request.query.get("kind")),
        })

    async def slo_get(request: web.Request) -> web.Response:
        return web.json_response(obs.slo.evaluate())

    async def history_get(request: web.Request) -> web.Response:
        # GET /api/metrics/history?series=&since=&step= — the telemetry
        # time machine: tiered-downsample rings for every tracked series
        # (obs/history.py). The sampler thread is started by
        # run_engine_server; under a bare test app the endpoint still
        # answers (empty points) rather than 404ing.
        try:
            kwargs = obs.history.parse_query(request.query)
        except ValueError as e:
            return web.json_response(
                {"error": {"message": f"bad query: {e}"}}, status=400
            )
        return web.json_response(obs.history.query(**kwargs))

    async def profile_capture(request: web.Request) -> web.Response:
        # POST /api/debug/profile?seconds=N — capture a jax.profiler
        # device trace around LIVE traffic for N seconds (blocking in a
        # worker thread; requests keep flowing), so a TPU window can
        # attribute the full-stack tax on chip without a bench harness.
        from ..utils.profiling import timed_capture

        try:
            seconds = float(request.query.get("seconds", "5"))
        except ValueError:
            return web.json_response(
                {"error": {"message": "seconds must be a number"}},
                status=400,
            )
        loop = asyncio.get_running_loop()
        try:
            logdir = await loop.run_in_executor(
                None, timed_capture, seconds
            )
        except ValueError as e:
            return web.json_response(
                {"error": {"message": str(e)}}, status=400
            )
        except RuntimeError as e:
            return web.json_response(
                {"error": {"message": str(e)}}, status=403
            )
        except Exception as e:  # noqa: BLE001 - already tracing / bad dir
            return web.json_response(
                {"error": {"message": str(e)}}, status=409
            )
        return web.json_response({
            "status": "captured", "seconds": seconds, "logdir": logdir,
        })

    # -- fleet data plane (serving/fleet): prefix digests for affinity
    # routing, chain park/export/import for replica-to-replica session
    # migration, and the drain notification. The wire format is the host
    # pool's token-chain keying (offload/pool.py), so imported pages
    # restore through the exact local offload-hit path.
    async def fleet_digests(request: web.Request) -> web.Response:
        eng = stack.engine
        loop = asyncio.get_running_loop()
        digests = await loop.run_in_executor(None, eng.prefix_digests)
        return web.json_response({
            "model": stack.model_name,
            "page_size": int(eng.cfg.page_size),
            "digests": digests,
        })

    async def fleet_park(request: web.Request) -> web.Response:
        try:
            body = await request.json()
            tokens = [int(t) for t in body.get("tokens") or []]
        except (json.JSONDecodeError, TypeError, ValueError):
            return web.json_response(
                {"error": {"message": "tokens must be an int list"}},
                status=400,
            )
        eng = stack.engine
        loop = asyncio.get_running_loop()

        def _park() -> int:
            n = eng.park_chain(tokens)
            eng.offload_flush()
            return n

        parked = await loop.run_in_executor(None, _park)
        return web.json_response({"parked_tokens": parked})

    async def fleet_kv_export(request: web.Request) -> web.Response:
        # Two body forms share the wire format:
        #   {"tokens": [...], "park": true}        single chain (migration)
        #   {"chains": [{"tokens": [...], "start_page": N}, ...],
        #    "park": false}                        batched (page fault-in)
        # park=True frees the chain's HBM pages after copying (the sender
        # is handing the session off); park=False replicates trie pages
        # into the host pool non-destructively — a peer fault-in must not
        # cost this replica its own cache.
        try:
            body = await request.json()
            chains = body.get("chains")
            if chains is None:
                chains = [{
                    "tokens": body.get("tokens") or [],
                    "start_page": 0,
                }]
            reqs = [
                (
                    [int(t) for t in c.get("tokens") or []],
                    max(0, int(c.get("start_page", 0))),
                )
                for c in chains
            ]
        except (json.JSONDecodeError, TypeError, ValueError,
                AttributeError):
            return web.json_response(
                {"error": {"message": "tokens must be an int list"}},
                status=400,
            )
        batched = body.get("chains") is not None
        park = bool(body.get("park", True))
        eng = stack.engine
        if getattr(eng, "offload", None) is None:
            empty = {"pages": [], "offload": False}
            if batched:
                empty = {
                    "results": [{"pages": []} for _ in reqs],
                    "offload": False,
                }
            return web.json_response(empty)
        from .fleet.transfer import pack_entries

        loop = asyncio.get_running_loop()

        def _export():
            out = []
            for tokens, start_page in reqs:
                if park:
                    eng.park_chain(tokens)
                    eng.offload_flush()
                else:
                    eng.replicate_chain(tokens)
                out.append(pack_entries(
                    eng.offload.pool.match(tokens, start_page=start_page)
                ))
            return out

        results = await loop.run_in_executor(None, _export)
        if batched:
            return web.json_response({
                "results": [{"pages": p} for p in results],
                "page_size": int(eng.cfg.page_size),
            })
        return web.json_response({
            "pages": results[0], "page_size": int(eng.cfg.page_size),
        })

    async def fleet_kv_import(request: web.Request) -> web.Response:
        try:
            body = await request.json()
            records = body.get("pages") or []
        except json.JSONDecodeError:
            return web.json_response(
                {"error": {"message": "invalid JSON body"}}, status=400
            )
        eng = stack.engine
        if getattr(eng, "offload", None) is None:
            return web.json_response({"imported": 0, "offload": False})
        from .fleet.transfer import unpack_entries

        loop = asyncio.get_running_loop()

        def _import() -> int:
            n = 0
            for toks, tree in unpack_entries(records, eng.cache):
                if eng.offload.pool.put(toks, tree):
                    n += 1
            return n

        imported = await loop.run_in_executor(None, _import)
        return web.json_response({"imported": imported})

    async def fleet_drain(request: web.Request) -> web.Response:
        # Router-initiated graceful drain notification: flips the
        # /healthz fleet block to draining. Admission gating is the
        # router's job (it stops routing here); in-flight work finishes.
        if membership is not None:
            membership.draining = True
        return web.json_response({
            "status": "draining",
            "running": len(stack.engine.sequences),
        })

    async def fleet_promote(request: web.Request) -> web.Response:
        # Autoscaler-initiated role change (standby -> decode): keeps the
        # replica's self-reported role in sync with the router registry
        # so a later full re-register doesn't demote it back to standby.
        try:
            body = await request.json()
        except json.JSONDecodeError:
            body = {}
        role = str(body.get("role", "decode"))
        if membership is not None:
            membership.promote(role)
        return web.json_response({
            "status": "ok",
            "role": membership.role if membership is not None else role,
        })

    app = web.Application(client_max_size=256 * 1024 * 1024)
    app.router.add_post("/v1/chat/completions", completions)
    app.router.add_get("/v1/models", models)
    app.router.add_get("/healthz", healthz)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/api/trace/{request_id}", trace_get)
    app.router.add_get("/api/timeline/{request_id}", timeline_get)
    app.router.add_get("/api/debug/flight", flight_get)
    app.router.add_get("/api/debug/memory", memory_profile)
    app.router.add_get("/api/slo", slo_get)
    app.router.add_get("/api/metrics/history", history_get)
    app.router.add_post("/api/debug/profile", profile_capture)
    app.router.add_post("/v1/profile/start", profile_start)
    app.router.add_post("/v1/profile/stop", profile_stop)
    app.router.add_get("/fleet/digests", fleet_digests)
    app.router.add_post("/fleet/park", fleet_park)
    app.router.add_post("/fleet/kv/export", fleet_kv_export)
    app.router.add_post("/fleet/kv/import", fleet_kv_import)
    app.router.add_post("/fleet/drain", fleet_drain)
    app.router.add_post("/fleet/promote", fleet_promote)
    return app


def run_engine_server(
    host: str = "0.0.0.0",
    port: int = 8000,
    model_name: str = "tiny-test",
    checkpoint: str = "",
    tokenizer: str = "",
    tp: int = 0,
    sp: int = 1,
    ep: int = 1,
    max_batch_size: int = 8,
    quantize: str = "",
    kv_quantize: str = "",
    offload: bool = False,
    async_depth: int = 2,
    join_fleet: str = "",
    advertise: str = "",
    replica_id: str = "",
    replica_role: str = "decode",
    restore_snapshot: str = "",
) -> None:
    from aiohttp import web

    from ..models.config import resolve_model

    if restore_snapshot:
        # Cold-start fast path: the snapshot IS the engine config —
        # model/engine flags on the command line are ignored (the
        # fingerprint check would refuse anything else anyway).
        if checkpoint or model_name != "tiny-test":
            log.warning(
                "--restore-snapshot overrides --model/--checkpoint: "
                "engine comes up exactly as snapshotted from %s",
                restore_snapshot,
            )
        engine = Engine.from_snapshot(restore_snapshot, warmup=True)
        model_name = engine.cfg.model
    else:
        model_name, model_cfg = resolve_model(model_name, checkpoint)
        if model_cfg is not None:
            log.info(
                "config.json -> %s: %dL d=%d heads=%d/%d vocab=%d",
                model_name, model_cfg.num_layers, model_cfg.hidden_size,
                model_cfg.num_heads, model_cfg.num_kv_heads,
                model_cfg.vocab_size,
            )

        cfg = EngineConfig(
            model=model_name,
            checkpoint=checkpoint,
            tokenizer=tokenizer,
            tp=tp,
            sp=sp,
            ep=ep,
            max_batch_size=max_batch_size,
            quantize=quantize,
            kv_quantize=kv_quantize,
            offload=offload,
            async_depth=async_depth,
            # Production server: compile everything before accepting requests
            # so no client ever pays XLA compile inside its TTFT.
            warmup=True,
        )
        engine = Engine(cfg, model_cfg=model_cfg)
    stack = ServingStack(engine)
    install_stack(model_name, stack)
    membership = None
    if join_fleet:
        from .fleet.client import FleetMembership

        membership = FleetMembership(
            stack,
            router_url=join_fleet,
            advertise_url=advertise or f"http://{host}:{port}",
            replica_id=replica_id,
            role=replica_role,
        )
        if getattr(engine, "offload", None) is not None:
            # Fleet-global KV: admission misses consult the router's
            # page directory and fault chains in peer-to-peer.
            from .fleet.pagestore import http_client

            engine.pagestore = http_client(
                join_fleet, membership.replica_id, engine
            )
    app = build_engine_app(stack, membership=membership)
    # Continuous SLO evaluation (GET /api/slo serves the same watchdog):
    # keeps the throughput rate window warm and logs breach transitions
    # into the flight ring even when nobody scrapes.
    obs.slo.get_watchdog().start()
    # Telemetry time machine: 1 Hz sampler behind /api/metrics/history
    # (tiered downsampling keeps it memory-bounded forever).
    obs.history.get_history().start()

    async def _announce(_) -> None:
        log.info("serving engine listening on %s:%d (model=%s)", host, port, model_name)
        if membership is not None:
            # Join AFTER the socket is bound: the router may probe the
            # advertised URL the moment the registration lands.
            membership.start()

    app.on_startup.append(_announce)
    web.run_app(app, host=host, port=port, print=None)
