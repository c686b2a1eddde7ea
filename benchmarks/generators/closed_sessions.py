"""Closed-loop sessions: the one general generator of both first mixes.

``sessions`` clients each run one session after another. A session is an
optional shared system prompt, a first user message and ``turns`` requests;
after each reply the client waits ``think_s`` (its tool running) and sends
the whole grown history with the next observation as a user message. With
``turns`` 1, no system prompt and no think time it is plain closed-loop
clients sending unshared prompts.

Every seed gets the SAME lengths in another order, and other text. The
lengths are stratified by wave: the ``sessions`` sessions that run side by
side hold, turn for turn, exactly the ``sessions`` mid-quantiles of each
distribution, shuffled by the seed. So any window of a run sees about the
same mix of short and long requests, whatever the seed and wherever the
window falls: the seed moves which client gets which size, not how much
work a run holds.
"""

from __future__ import annotations

import asyncio
import math
import random
import statistics
import time

ALPHABET = "abcdefghijklmnopqrstuvwxyz0123456789      -_/.:="


def _quantile(dist: dict, u: float) -> int:
    if dist["dist"] == "uniform":
        x = dist["lo"] + u * (dist["hi"] - dist["lo"])
    elif dist["dist"] == "lognormal":
        z = statistics.NormalDist().inv_cdf(u)
        x = math.exp(math.log(dist["median"]) + dist["sigma"] * z)
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return int(min(dist["hi"], max(dist["lo"], round(x))))


def stratified(dist: dict, n: int, rng: random.Random) -> list[int]:
    """n values at the mid-quantiles of ``dist``, shuffled by ``rng``."""
    values = [_quantile(dist, (i + 0.5) / n) for i in range(n)]
    rng.shuffle(values)
    return values


def text(rng: random.Random, n: int) -> str:
    return "".join(rng.choices(ALPHABET, k=n))


def plan(params: dict, seed: int) -> dict:
    """The whole run's requests as lengths and text seeds."""
    rng = random.Random(seed)
    width, turns = params["sessions"], params["turns"]
    sessions = []
    for _wave in range(-(-params["planned_sessions"] // width)):
        first = stratified(params["first_user_tokens"], width, rng)
        budgets = [stratified(params["max_tokens"], width, rng)
                   for _ in range(turns)]
        obs = [stratified(params["observation_tokens"], width, rng)
               for _ in range(turns - 1)]
        sessions += [
            {
                "text_seed": rng.getrandbits(48),
                "first_user": first[j],
                "max_tokens": [b[j] for b in budgets],
                "observations": [o[j] for o in obs],
            }
            for j in range(width)
        ]
    return {
        "system": text(random.Random(seed ^ 0x5EED), params["system_tokens"]),
        "sessions": sessions,
    }


async def setup(params: dict, seed: int, send) -> None:
    """Admit the shared system prompt once, so that sessions find it."""
    if params["system_tokens"]:
        shared = plan(params, seed)["system"]
        rec = await send({
            "messages": [{"role": "system", "content": shared}],
            "max_tokens": 1,
        }, {"phase": "setup"})
        if not rec.ok:
            raise RuntimeError(f"shared prompt not admitted: {rec.error}")


async def run(params: dict, seed: int, send, window) -> None:
    """Drive the sessions until cancelled. ``window.ready()`` is called
    once every client has had its first token: every decode row is then in
    use, which is the state the window measures."""
    planned = plan(params, seed)
    counter = iter(range(10**9))
    warm = set()

    def is_warm(slot: int) -> None:
        warm.add(slot)
        if len(warm) == params["sessions"]:
            window.ready()

    async def session(slot: int, k: int) -> None:
        s = planned["sessions"][k % len(planned["sessions"])]
        rng = random.Random(s["text_seed"] + (k // len(planned["sessions"])))
        messages = []
        if planned["system"]:
            messages.append({"role": "system", "content": planned["system"]})
        messages.append({"role": "user",
                         "content": text(rng, s["first_user"])})
        for turn in range(params["turns"]):
            body = {"messages": messages, "max_tokens": s["max_tokens"][turn]}
            if params.get("response_format"):
                body["response_format"] = params["response_format"]
            rec = await send(body, {"session": k, "turn": turn, "slot": slot},
                             lambda: is_warm(slot))
            if not rec.ok:
                await asyncio.sleep(0.1)   # a dead server must not spin us
                return
            if turn + 1 == params["turns"]:
                return
            due = time.perf_counter() + params["think_s"]
            await asyncio.sleep(params["think_s"])
            window.late(time.perf_counter() - due)
            messages = messages + [
                {"role": "assistant", "content": rec.text},
                {"role": "user",
                 "content": text(rng, s["observations"][turn])},
            ]

    async def client(slot: int) -> None:
        await asyncio.sleep(slot * params["stagger_s"])
        while True:
            await session(slot, next(counter))

    await asyncio.gather(*(client(i) for i in range(params["sessions"])))
