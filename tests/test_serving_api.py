"""Serving stack tests: scheduler continuous batching, OpenAI frontend,
SSE streaming, tool_calls parsing, and the end-to-end agent-over-tpu://
slice with zero external API calls."""

import asyncio
import json
import os
import threading

import jax.numpy as jnp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from opsagent_tpu.serving.api import (
    ServingStack,
    build_engine_app,
    install_stack,
    _stacks,
)
from opsagent_tpu.serving.engine import Engine, EngineConfig
from opsagent_tpu.serving.sampler import SamplingParams
from opsagent_tpu.serving.scheduler import Scheduler, Request


@pytest.fixture(scope="module")
def stack():
    cfg = EngineConfig(
        model="tiny-test",
        dtype=jnp.float32,
        tp=1,
        page_size=4,
        num_pages=128,
        max_pages_per_seq=16,
        max_batch_size=4,
        prefill_buckets=(32, 64),
        max_new_tokens_default=8,
    )
    s = ServingStack(Engine(cfg))
    install_stack("tiny-test", s)
    yield s
    s.close()
    _stacks.pop("tiny-test", None)


def test_scheduler_many_concurrent(stack):
    """16 concurrent sessions through a batch-4 engine all complete."""
    results = {}
    errors = []

    def worker(i):
        try:
            toks = stack.scheduler.complete(
                [257, i % 200 + 1, 2, 3], SamplingParams(max_tokens=4),
                timeout_s=300,
            )
            results[i] = toks
        except Exception as e:  # noqa: BLE001
            errors.append((i, repr(e)))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert len(results) == 16
    assert all(1 <= len(v) <= 4 for v in results.values())


def test_chat_completion_shape(stack):
    resp = stack.chat_completion(
        {
            "model": "tiny-test",
            "messages": [
                {"role": "system", "content": "sys"},
                {"role": "user", "content": "hello"},
            ],
            "max_tokens": 4,
        }
    )
    assert resp["object"] == "chat.completion"
    choice = resp["choices"][0]
    assert choice["message"]["role"] == "assistant"
    assert choice["finish_reason"] in ("stop", "length")
    assert resp["usage"]["prompt_tokens"] > 0
    assert 1 <= resp["usage"]["completion_tokens"] <= 4


def test_tool_calls_parsing(stack):
    text = json.dumps(
        {
            "tool_calls": [
                {
                    "id": "call_9",
                    "type": "function",
                    "function": {
                        "name": "kubectl",
                        "arguments": "{\"command\": \"get ns\"}",
                    },
                }
            ]
        }
    )
    calls = stack._parse_tool_calls(text)
    assert calls[0]["function"]["name"] == "kubectl"
    assert json.loads(calls[0]["function"]["arguments"])["command"] == "get ns"
    assert stack._parse_tool_calls("plain text") is None
    # dict-valued arguments are normalized to a JSON string
    calls = stack._parse_tool_calls(
        '{"tool_calls": [{"function": {"name": "f", "arguments": {"a": 1}}}]}'
    )
    assert json.loads(calls[0]["function"]["arguments"]) == {"a": 1}


def test_http_completions_and_stream(stack):
    app = build_engine_app(stack)

    async def scenario():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.get("/v1/models")
            assert (await r.json())["data"][0]["id"] == "tiny-test"

            r = await client.get("/healthz")
            health = await r.json()
            assert health["status"] == "ok"
            assert health["prefix_evictions"] >= 0  # counter exposed

            r = await client.post(
                "/v1/chat/completions",
                json={
                    "messages": [{"role": "user", "content": "hi"}],
                    "max_tokens": 3,
                },
            )
            assert r.status == 200
            data = await r.json()
            assert data["choices"][0]["message"]["role"] == "assistant"

            r = await client.post(
                "/v1/chat/completions",
                json={
                    "messages": [{"role": "user", "content": "hi"}],
                    "max_tokens": 3,
                    "stream": True,
                },
            )
            assert r.status == 200
            body = await r.text()
            lines = [ln for ln in body.splitlines() if ln.startswith("data: ")]
            assert lines[-1] == "data: [DONE]"
            first = json.loads(lines[0][len("data: ") :])
            assert first["object"] == "chat.completion.chunk"
            finals = json.loads(lines[-2][len("data: ") :])
            assert finals["choices"][0]["finish_reason"] in ("stop", "length")

            r = await client.post("/v1/chat/completions", json={})
            assert r.status == 400
        finally:
            await client.close()

    asyncio.get_event_loop_policy().new_event_loop().run_until_complete(scenario())


def test_agent_over_tpu_provider_end_to_end(fake_tools):
    """The reference's whole raison d'être, in-tree: the ReAct agent loop
    running against the TPU engine through the tpu:// scheme — zero external
    API calls. The loop requests schema-constrained decoding, so even random
    tiny weights emit parseable ToolPrompt JSON: every assistant turn in the
    transcript must parse, proving agent -> provider -> engine -> FSM-masked
    sampler -> detokenize end to end."""
    import json as _json

    from opsagent_tpu.agent.react import assistant_with_config
    from opsagent_tpu.serving.api import ServingStack, install_stack, _stacks
    from opsagent_tpu.serving.engine import Engine, EngineConfig

    cfg = EngineConfig(
        model="tiny-test", dtype=jnp.float32, tp=1, page_size=8,
        num_pages=256, max_pages_per_seq=128, max_batch_size=2,
        prefill_buckets=(256, 512, 1024), max_new_tokens_default=48,
    )
    s = ServingStack(Engine(cfg))
    install_stack("tiny-agent", s)
    try:
        fake_tools({})
        messages = [
            {"role": "system", "content": "you are a test agent"},
            {"role": "user", "content": "count namespaces"},
        ]
        out, history = assistant_with_config(
            "tpu://tiny-agent", messages, max_tokens=48, max_iterations=2
        )
        assert isinstance(out, str)
        assert history[-1]["role"] == "assistant"
        # Constrained decoding guarantees every emitted byte stays inside
        # the ToolPrompt schema's language: a completed reply parses, a
        # length-capped one is still a valid prefix (live DFA state).
        from opsagent_tpu.serving.constrained import (
            TOOLPROMPT_SCHEMA, compile_regex, schema_to_regex,
        )

        from opsagent_tpu.agent.prompts import SUMMARIZE_PROMPT

        dfa = compile_regex(schema_to_regex(TOOLPROMPT_SCHEMA))
        checked = 0
        for i, msg in enumerate(history):
            if msg["role"] != "assistant":
                continue
            # The summarization turn (triggered when a length-capped reply
            # does not parse as a ToolPrompt) is INTENTIONALLY free-form —
            # no response_format — so whether it appears depends on where
            # the 48-token budget cut the constrained replies
            # (weight-dependent). Only constrained turns carry the
            # stays-in-language guarantee.
            if (
                i > 0 and history[i - 1]["role"] == "user"
                and history[i - 1]["content"] == SUMMARIZE_PROMPT
            ):
                continue
            checked += 1
            state = dfa.run(dfa.start, msg["content"].encode())
            assert state >= 0, f"escaped the schema: {msg['content']!r}"
            try:
                parsed = _json.loads(msg["content"])
                assert set(parsed) <= {
                    "question", "thought", "action", "observation",
                    "final_answer",
                }
            except _json.JSONDecodeError:
                assert not dfa.accept[state]  # truncated, not malformed
        assert checked >= 1  # the constrained path actually ran
    finally:
        s.close()
        _stacks.pop("tiny-agent", None)


def test_prompt_too_long_fails_fast(stack):
    """A prompt that can never fit must be rejected immediately with a clear
    error, not spin in the admission queue until timeout."""
    import time

    huge = [257] + [65] * 100  # > largest bucket (64) of the test engine
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="exceeds|pages"):
        stack.scheduler.complete(huge, SamplingParams(max_tokens=2), timeout_s=30)
    assert time.perf_counter() - t0 < 5.0


def test_stop_strings(stack):
    text, finish = stack._finalize_text(
        [72, 101, 108, 108, 111, 33], stop=("llo",)
    )
    assert text == "He"
    assert finish == "stop"


def test_prompt_too_long_http_status_400(stack):
    """PromptTooLong is a permanent client error: the HTTP frontend must
    return 400 (derived from the typed RequestError, not string matching)."""
    app = build_engine_app(stack)

    async def scenario():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.post(
                "/v1/chat/completions",
                json={
                    "messages": [{"role": "user", "content": "x" * 500}],
                    "max_tokens": 2,
                },
            )
            assert r.status == 400, await r.text()
        finally:
            await client.close()

    asyncio.get_event_loop_policy().new_event_loop().run_until_complete(scenario())


def test_failed_admission_does_not_leak_pages(stack):
    """A request whose admission blows up mid-prefill (raising mask_fn fires
    during first-token sampling) must free its pages."""
    free_before = stack.engine.alloc.free_pages

    def bad_mask(_tokens):
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError, match="admission failed|boom"):
        stack.scheduler.complete(
            [257, 1, 2, 3], SamplingParams(max_tokens=2),
            mask_fn=bad_mask, timeout_s=30,
        )
    assert stack.engine.alloc.free_pages == free_before
    assert not stack.engine.sequences


class _ScriptedScheduler:
    """Feeds a scripted token list through on_token, then completes."""

    def __init__(self, tokens):
        self.tokens = tokens

    def submit(self, req):
        for t in self.tokens:
            if req.on_token:
                req.on_token(t)
        req.finish_reason = "length"
        req.done.set()
        return req


class _FakeEngine:
    def __init__(self):
        from opsagent_tpu.serving.tokenizer import ByteTokenizer

        self.tokenizer = ByteTokenizer()
        self.cfg = EngineConfig(model="tiny-test")
        self.model_cfg = type("M", (), {"name": "tiny-test"})()


def _scripted_stack(tokens):
    s = ServingStack.__new__(ServingStack)
    s.engine = _FakeEngine()
    s.scheduler = _ScriptedScheduler(tokens)
    s.model_name = "tiny-test"
    return s


def test_an_open_stream_holds_no_worker_between_tokens():
    """More streams than the event loop's executor has workers (at most 32)
    all stay open at once: the scheduler here hands out no token until all
    40 requests are in, which a stream that waited for its first token in a
    worker would never see."""
    want, text = 40, "ok then"

    class Gated:
        def __init__(self):
            self.held, self.lock = [], threading.Lock()

        def submit(self, req):
            with self.lock:
                self.held.append(req)
                go = self.held if len(self.held) == want else []
            for r in go:
                for t in text.encode():
                    r.on_token(t)
                r.finish_reason = "length"
                r.done.set()
            return req

    s = _scripted_stack([])
    s.scheduler = Gated()
    app = build_engine_app(s)

    async def scenario():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            async def one(i):
                r = await client.post("/v1/chat/completions", json={
                    "messages": [{"role": "user", "content": f"q{i}"}],
                    "stream": True})
                assert r.status == 200
                lines = [json.loads(ln[6:]) for ln in (await r.text())
                         .splitlines() if ln.startswith("data: {")]
                return "".join(c["choices"][0]["delta"].get("content", "")
                               for c in lines)

            got = await asyncio.wait_for(
                asyncio.gather(*(one(i) for i in range(want))), 60)
            assert got == [text] * want
        finally:
            await client.close()

    asyncio.get_event_loop_policy().new_event_loop().run_until_complete(
        scenario())


def test_stream_stop_string_straddles_chunks():
    """Stop-string holdback: 'END' arriving one byte per token must still be
    caught, and nothing after (or of) the stop string is emitted."""
    text = "Hello END tail"
    s = _scripted_stack(list(text.encode()))
    chunks = list(
        s.chat_completion_stream(
            {"messages": [{"role": "user", "content": "q"}], "stop": ["END"]}
        )
    )
    content = "".join(
        c["choices"][0]["delta"].get("content", "")
        for c in chunks
        if "choices" in c
    )
    assert content == "Hello "
    assert chunks[-1]["choices"][0]["finish_reason"] == "stop"


def test_stream_multibyte_char_split_across_tokens():
    """A UTF-8 char whose bytes span tokens must be withheld until complete,
    then emitted exactly once."""
    text = "日本語 ok"
    s = _scripted_stack(list(text.encode("utf-8")))
    chunks = list(
        s.chat_completion_stream({"messages": [{"role": "user", "content": "q"}]})
    )
    content = "".join(
        c["choices"][0]["delta"].get("content", "")
        for c in chunks
        if "choices" in c
    )
    assert content == text
    assert "�" not in content


def test_tpu_scheme_lazy_registration_fresh_process():
    """In a fresh process that never imports the serving stack, the agent's
    ChatClient must still resolve --model tpu://<name> (the provider module
    is imported lazily on first use)."""
    import subprocess
    import sys

    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "from opsagent_tpu.llm.client import ChatClient\n"
        "import sys\n"
        "assert not any('serving' in m for m in sys.modules), 'not lazy'\n"
        "r = ChatClient().chat_completion(\n"
        "    'tpu://tiny-test', [{'role': 'user', 'content': 'hi'}], max_tokens=2)\n"
        "assert r['choices'][0]['message'] is not None\n"
        "print('LAZY_OK')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=300, cwd="/root/repo",
    )
    assert "LAZY_OK" in out.stdout, out.stderr[-2000:]


def test_stream_bad_sampling_param_returns_json_error(stack):
    """A translation error on a stream=true request must return a JSON error
    status, not a dead SSE connection."""
    app = build_engine_app(stack)

    async def scenario():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.post(
                "/v1/chat/completions",
                json={
                    "messages": [{"role": "user", "content": "hi"}],
                    "max_tokens": "many",
                    "stream": True,
                },
            )
            assert r.status == 400
            assert "error" in await r.json()
        finally:
            await client.close()

    asyncio.get_event_loop_policy().new_event_loop().run_until_complete(scenario())


def test_stream_prompt_too_long_http_status_400(stack):
    """An ADMISSION error (prompt exceeds the largest prefill bucket) on a
    stream=true request must surface as HTTP 400, not a 200 SSE stream with
    an in-stream error event."""
    app = build_engine_app(stack)

    async def scenario():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.post(
                "/v1/chat/completions",
                json={
                    "messages": [{"role": "user", "content": "x" * 500}],
                    "max_tokens": 2,
                    "stream": True,
                },
            )
            assert r.status == 400, await r.text()
            assert "error" in await r.json()
        finally:
            await client.close()

    asyncio.get_event_loop_policy().new_event_loop().run_until_complete(scenario())


def test_raising_stream_callback_does_not_leak_pages(stack):
    """A stream/on_token callback that raises on the FIRST token (delivered
    during admission) must not leak pages or a zombie Sequence."""
    free_before = stack.engine.alloc.free_pages

    def bad_stream(_tok):
        raise RuntimeError("stream boom")

    with pytest.raises(RuntimeError, match="admission failed|stream boom"):
        stack.scheduler.complete(
            [257, 1, 2, 3], SamplingParams(max_tokens=2),
            on_token=bad_stream, timeout_s=30,
        )
    assert stack.engine.alloc.free_pages == free_before
    assert not stack.engine.sequences


def test_multibyte_stop_string_halts_engine_side(stack):
    """A CJK stop string (3 UTF-8 byte-tokens per char) must stop generation
    engine-side well before max_tokens (token window sized in bytes)."""
    from opsagent_tpu.serving.engine import Sequence

    seq = Sequence(seq_id=0, prompt_len=1, params=SamplingParams(stop=("終了" * 5,)))
    seq.tokens = list(("x" + "終了" * 5).encode("utf-8"))
    assert stack.engine._hit_stop_string(seq)


def test_profile_endpoints(stack, tmp_path, monkeypatch):
    """/v1/profile/{start,stop}: operator-gated jax.profiler capture around
    live traffic. Without --profile-dir the start endpoint refuses (403) —
    a network client must not get a filesystem-write primitive; with it, a
    start/traffic/stop cycle writes a capture and double-stop is a 409."""
    app = build_engine_app(stack)

    async def scenario():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            monkeypatch.delenv("OPSAGENT_PROFILE_DIR", raising=False)
            r = await client.post("/v1/profile/start", json={"logdir": "/etc"})
            assert r.status == 403  # client-supplied logdir is never honored

            logdir = str(tmp_path / "trace")
            monkeypatch.setenv("OPSAGENT_PROFILE_DIR", logdir)
            r = await client.post("/v1/profile/start")
            assert r.status == 200
            assert (await r.json())["logdir"] == logdir

            r = await client.post(
                "/v1/chat/completions",
                json={"messages": [{"role": "user", "content": "hi"}],
                      "max_tokens": 2},
            )
            assert r.status == 200

            r = await client.post("/v1/profile/stop")
            assert r.status == 200
            files = [
                os.path.join(root, f)
                for root, _, fs in os.walk(logdir) for f in fs
            ]
            assert files, "trace capture wrote no files"

            r = await client.post("/v1/profile/stop")
            assert r.status == 409  # not tracing
        finally:
            await client.close()

    asyncio.get_event_loop_policy().new_event_loop().run_until_complete(scenario())
