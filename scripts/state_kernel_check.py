"""The state kernel alone on a chip, against the XLA forms.

    chiprun -- python scripts/state_kernel_check.py [cell3|cell4 ...]

Prints one JSON line a case, at the state cells' shapes: the largest
differences of the read-out, the slots and the conv tails from
``delta_rule_chunk`` / ``delta_rule_step`` on the same slots, through
``LAYERS`` calls in one program as a step makes them. What interpret mode
cannot show (DMA, semaphores, the tiling) shows here. It takes no time:
the kernel's time is the ``lin_scan`` scope's in a traced benchmark run
(timed alone, this program read 7-9 ms a call where the engine's step
reads 2.1: PERF.md section 6, PR 37).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from opsagent_tpu.ops import linear_state_pallas as lsp  # noqa: E402
from opsagent_tpu.ops.linear_attention import (  # noqa: E402
    delta_rule_chunk,
    delta_rule_step,
)

LAYERS = 6
CASES = {
    # name: (B, S, H, dk, dv, decay a channel, slots a layer, conv width,
    #        rows' valid counts)
    "cell3.mixed": (32, 16, 64, 128, 128, True, 128, 73728,
                    [1] * 16 + [16] * 12 + [0] * 4),
    "cell3.lanes": (32, 16, 64, 128, 128, True, 128, 73728, [1] * 32),
    "cell3.chunks": (32, 16, 64, 128, 128, True, 128, 73728, [16] * 32),
    "cell3.block": (32, 1, 64, 128, 128, True, 128, 73728, [1] * 32),
    "cell4.mixed": (16, 16, 30, 96, 192, False, 48, 34560,
                    [1] * 12 + [16] * 2 + [0] * 2),
    "cell4.block": (16, 1, 30, 96, 192, False, 48, 34560, [1] * 16),
}


def run(name):
    B, S, H, dk, dv, chan, slots, W, valid = CASES[name]
    P = lsp.heads_packed(dv)
    rng = np.random.default_rng(7)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q, k, v = f(B, S, H, dk), f(B, S, H, dk), f(B, S, H, dv)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.abs(f(B, S, H, dk) if chan else f(B, S, H)) * 0.3
    beta = jax.nn.sigmoid(f(B, S, H)) * 2
    R = lsp.conv_slot_shape(W)[0]
    state = jax.random.normal(
        jax.random.PRNGKey(1), (LAYERS * slots, H // P, dk, P * dv))
    conv = jnp.zeros((LAYERS * slots, R, 128), jnp.bfloat16)
    tail = jnp.asarray(rng.standard_normal((B, W)), jnp.bfloat16)
    valid = jnp.asarray(valid, jnp.int32)
    live = jnp.asarray(rng.permutation(slots - B)[:B], jnp.int32)
    snap = jnp.where(jnp.arange(B) % 5 == 0, slots - 1 - jnp.arange(B), -1)
    snap = jnp.where(valid > 0, snap, -1).astype(jnp.int32)
    fresh = jnp.arange(B) % 7 == 3

    def held_to_heads(a):
        n = a.shape[0]
        return a.reshape(n, H // P, dk, P, dv).transpose(0, 1, 3, 2, 4).reshape(
            n, H, dk, dv)

    @jax.jit
    def oracle(state):
        S0 = jnp.where(fresh[:, None, None, None], 0.0,
                       held_to_heads(state[live]))
        if S == 1:
            on = valid > 0
            return delta_rule_step(
                q[:, 0], k[:, 0], v[:, 0],
                jnp.where(on.reshape(B, *([1] * (g.ndim - 2))), g[:, 0], 0.0),
                jnp.where(on[:, None], beta[:, 0], 0.0), S0)
        return delta_rule_chunk(q, k, v, g, beta, S0, valid)

    want_o, want_S = oracle(state)
    want_o = want_o[:, None] if S == 1 else want_o

    def layers(state, conv, q, k, v, g, beta, tail):
        outs = []
        for l in range(LAYERS):
            o, state, conv = lsp.delta_rule_slots(
                q, k, v, g, beta, state, conv, tail, live + l * slots,
                jnp.where(snap >= 0, snap + l * slots, -1), fresh, valid)
            outs.append(o)
        return outs[0], state, conv

    # the tokens' tensors are arguments: closed over, they would be
    # constants of the program, and 80 MB of them cost more than the kernel
    layers = jax.jit(layers, donate_argnums=(0, 1))

    def step(state, conv):
        return layers(state, conv, q, k, v, g, beta, tail)

    before = np.asarray(held_to_heads(state[:slots]))
    o, state, conv = jax.block_until_ready(step(state, conv))
    after = np.asarray(held_to_heads(state[:slots]))
    real = np.asarray(jnp.arange(S)[None, :] < valid[:, None])
    err_o = float(np.max(np.abs(np.asarray(o - want_o))[real]))
    expect = before.copy()
    for b in range(B):
        if int(valid[b]) > 0:
            expect[int(live[b])] = np.asarray(want_S[b])
            if int(snap[b]) >= 0:
                expect[int(snap[b])] = np.asarray(want_S[b])
    err_s = float(np.max(np.abs(after - expect)))
    got_tail = np.asarray(conv[:slots].reshape(slots, -1)[:, :W], np.float32)
    wrote = [int(live[b]) for b in range(B) if int(valid[b]) > 0]
    err_c = float(np.max(np.abs(
        got_tail[wrote] - np.asarray(tail, np.float32)[
            [b for b in range(B) if int(valid[b]) > 0]])))
    dev = jax.devices()[0]
    line = {
        "case": name, "platform": dev.platform, "device_kind": dev.device_kind,
        "err_o": err_o, "err_state": err_s, "err_conv": err_c,
        "heads_a_block": lsp.block_heads(H, P, dk, dv, lsp._round_up(
            S, 8 if S <= 8 else lsp.TOKEN_GROUP)),
    }
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    for name in [c for c in CASES
                 if not sys.argv[1:] or any(c.startswith(a) for a in sys.argv[1:])]:
        run(name)
