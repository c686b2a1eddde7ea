"""Mamba-1's selective scan in plain ``jax.numpy`` under XLA: the mixer of
Jamba's state-space layers, and the oracle of everything else that
computes it (``selective_scan_pallas``).

Per channel ``c`` of ``d_inner`` and state index ``n`` of ``d_state``, with
a float32 state ``h`` held ``[d_state, d_inner]`` (the channels on the TPU's
128 lanes, the 16 state indices on its sublanes: ``[d_inner, 16]`` would
pad the 16 to a whole lane tile, eight times the bytes held and moved):

    h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] B_t[n] x_t[c]
    y_t[c]    = sum_n C_t[n] h_t[n, c]

``A`` is negative (``-exp(A_log)``) and ``dt`` a softplus's, so every decay
factor lies in (0, 1]: a diagonal decay with no delta update. The skip
``D x_t`` and the gate are the caller's. Two forms over the same
mathematics:

- ``selective_scan_step``: one token for each row, the recurrence as
  written (decode lanes, the fused decode block).
- ``selective_scan``: ``S`` slots of each row from the row's stored state,
  a ``lax.scan`` over time. Rows are ragged: a slot at or past a row's
  ``valid`` count gets ``dt = 0``, which leaves the state as it was to the
  bit (a decay of one, an input of zero); its ``y`` is not read.

Everything here is float32 and elementwise, on the vector unit: nothing
goes through the matrix unit, so there is no matmul precision to set. The
state is the sequence's memory, and an error in it decays only as fast as
the state does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def selective_scan_step(x, dt, A, Bm, Cm, state):
    """One token a row. x, dt: [B, C]; A: [N, C]; Bm, Cm: [B, N]; state:
    [B, N, C]. Returns (y [B, C], new state)."""
    decay = jnp.exp(dt[:, None, :] * A)
    state = decay * state + (dt * x)[:, None, :] * Bm[:, :, None]
    return jnp.sum(state * Cm[:, :, None], axis=1), state


def selective_scan(x, dt, A, Bm, Cm, state, valid):
    """``S`` slots a row from the row's state. x, dt: [B, S, C]; A: [N, C];
    Bm, Cm: [B, S, N]; state: [B, N, C]; valid: [B] real slots of each row
    (the rest leave the state untouched). Returns (y [B, S, C], new
    state)."""
    real = jnp.arange(x.shape[1])[None, :] < valid[:, None]      # [B, S]
    dt = jnp.where(real[:, :, None], dt, 0.0)

    def token(h, xs):
        x_t, dt_t, b_t, c_t = xs
        y, h = selective_scan_step(x_t, dt_t, A, b_t, c_t, h)
        return h, y

    state, y = jax.lax.scan(
        token, state, tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1), state
