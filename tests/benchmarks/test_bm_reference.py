"""The plain reference against the engine on tiny Qwen2 shapes, on the CPU.

The engine serves the benchmark's seeded weights; its greedy tokens, run
through ``check.run_check``, must sit within the limit, and must not when
a token is altered or (the control) when the reference itself is rounded to
int4 and put in the program's place. (int8 pages in a float32 engine of this
size flip no greedy token in 72, so a served-token check cannot tell them
apart here: PERF.md lists that under Open questions.)
"""

import os

import numpy as np
import pytest

from benchmarks import check, tokens
from benchmarks.loading import load_data

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEEDS = (3, 2**31 + 77, 12345)


@pytest.fixture(scope="module")
def tiny():
    return load_data(os.path.join(
        ROOT, "benchmarks", "configs", "qwen25-7b-int8.json"), rehearse=True)


def serve(config: dict, seed: int) -> list[dict]:
    """Greedy replies of the engine to three seeded prompts."""
    import jax.numpy as jnp

    from benchmarks import server
    from opsagent_tpu.serving.engine import Engine, EngineConfig
    from opsagent_tpu.serving.sampler import SamplingParams

    mc = server.model_config(config)
    eng = Engine(
        EngineConfig(model=config["preset"], dtype=jnp.float32, tp=1,
                     quantize="int8",
                     max_batch_size=4, num_pages=128, max_pages_per_seq=16,
                     prefill_buckets=(64,), mixed_buckets=(16,)),
        model_cfg=mc, params=server.program_tree(config, seed),
        params_quantized=True, tokenizer=server.bench_tokenizer(mc.vocab_size),
    )
    rng = np.random.default_rng(seed % 1000)
    prompts = [tokens.template_ids([{"role": "user", "content": tokens.decode(
        rng.integers(32, 127, size=n))}]) for n in (40, 23, 57)]
    replies = eng.generate(prompts, SamplingParams(temperature=0.0, max_tokens=24))
    return [{"prompt_ids": p, "reply_ids": [t for t in r if t != tokens.EOS],
             "constrained": False} for p, r in zip(prompts, replies)]


@pytest.mark.parametrize("seed", SEEDS)
def test_engine_tokens_sit_on_the_reference_and_the_control_does_not(tiny, seed):
    limits = tiny["check"]["limits"]
    numbers = check.run_check(tiny, seed, serve(tiny, seed), control_bits=4)
    assert numbers["checked_tokens"] >= 40   # a reply may end early at EOS
    ok, lines = check.verdict(numbers, limits)
    assert ok, lines
    assert numbers["agree_share"] == 1.0
    # the control: int4 weights in the program's place are NOT correct
    control = dict(numbers, **numbers["control"])
    assert not check.verdict(control, limits)[0]
    assert numbers["control"]["gap_max"] > 3 * max(numbers["gap_max"], limits["gap_max"])


def test_an_altered_token_falls_outside_the_limit(tiny):
    samples = serve(tiny, SEEDS[0])
    samples[1]["reply_ids"][5] = (samples[1]["reply_ids"][5] + 1) % 512
    numbers = check.run_check(tiny, SEEDS[0], samples)
    assert not check.verdict(numbers, tiny["check"]["limits"])[0]
