"""HTTP/SSE client of the served path, on the load generator's own clock.

Never imports JAX. ``stream_chat`` times one streamed
``/v1/chat/completions`` request: when it was sent, when each content chunk
arrived and how many tokens it held (the benchmark's tokenizer renders one
token as one character), and how it finished. ``scrape`` reads ``/metrics``
into ``{family: [(labels, value)]}``.
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass, field

import aiohttp


@dataclass
class Record:
    """One request as the client saw it."""
    meta: dict
    messages: list
    max_tokens: int
    constrained: bool
    t_send: float = 0.0
    t_done: float = 0.0                          # 0.0 while in flight
    chunks: list = field(default_factory=list)   # (arrival, tokens)
    text: str = ""
    finish_reason: str = ""
    error: str = ""

    @property
    def tokens(self) -> int:
        return len(self.text)

    @property
    def ok(self) -> bool:
        return not self.error and self.tokens > 0 and bool(self.finish_reason)


def new_record(body: dict, meta: dict) -> Record:
    return Record(
        meta=meta, messages=[dict(m) for m in body["messages"]],
        max_tokens=body["max_tokens"],
        constrained=bool(body.get("response_format")),
    )


async def stream_chat(session: aiohttp.ClientSession, base: str, body: dict,
                      rec: Record, on_first=None) -> Record:
    """Send ``body`` streamed and fill ``rec`` as the chunks arrive;
    ``on_first()`` is called when the first token has."""
    rec.t_send = time.perf_counter()
    try:
        async with session.post(
            base + "/v1/chat/completions", json=dict(body, stream=True)
        ) as resp:
            if resp.status != 200:
                rec.error = f"HTTP {resp.status}: {(await resp.text())[:300]}"
                return rec
            async for raw in resp.content:
                line = raw.decode("utf-8").strip()
                if not line.startswith("data:"):
                    continue
                payload = line[5:].strip()
                if payload == "[DONE]":
                    break
                event = json.loads(payload)
                if "error" in event:
                    rec.error = str(event["error"])[:300]
                    break
                choice = event["choices"][0]
                content = choice.get("delta", {}).get("content")
                if content:
                    rec.chunks.append((time.perf_counter(), len(content)))
                    rec.text += content
                    if on_first and len(rec.chunks) == 1:
                        on_first()
                if choice.get("finish_reason"):
                    rec.finish_reason = choice["finish_reason"]
    except (aiohttp.ClientError, ConnectionError, ValueError, KeyError) as e:
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        rec.t_done = time.perf_counter()
    if not rec.error and not rec.finish_reason:
        rec.error = "stream ended without a finish_reason"
    return rec


def parse_metrics(text: str) -> dict:
    """Prometheus exposition -> {sample name: [(labels, value)]}."""
    out: dict[str, list] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = {}
        for part in rest.rstrip("}").split(","):
            k, eq, v = part.partition("=")
            if eq:
                labels[k.strip()] = v.strip().strip('"')
        try:
            out.setdefault(name, []).append((labels, float(value)))
        except ValueError:
            continue
    return out


async def scrape(session: aiohttp.ClientSession, base: str) -> dict:
    async with session.get(base + "/metrics") as resp:
        return parse_metrics(await resp.text())


def total(metrics: dict, name: str, **labels) -> float:
    """Sum of a sample name's values whose labels include ``labels``;
    0.0 where the program has not recorded the family yet."""
    return sum(
        v for ls, v in metrics.get(name, [])
        if all(ls.get(k) == want for k, want in labels.items())
    )


def delta(before: dict, after: dict, name: str, **labels) -> float:
    return total(after, name, **labels) - total(before, name, **labels)
