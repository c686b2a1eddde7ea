"""Mamba-1's selective scan over state slots, Pallas TPU ("pallas-ssm").

What a Mamba layer of a serving step does to its rows' recurrent state
where the code chooses it (``ops.kernels.ssm_state_backend``). Under XLA
(``ops.selective_scan``, the oracle) a mixed step's scan walks every slot of
every row: a ``lax.scan`` whose carry is all the rows' states, read and
written once a SLOT (16 passes over 21 MB a layer at 64 rows x 16 slots,
whatever the rows carry), beside the slot's gather and its two scatters.
This kernel takes a row's state from read through its own tokens to both
writes, as ``linear_state_pallas`` does for the delta rule:

- **Once in, once out.** Grid ``(rows, channel blocks)``; the flat slot of
  each row, its snapshot target, ``fresh`` and ``valid`` are scalar-
  prefetched. A block of the row's channels ``[d_state, Cb]`` is copied
  from ``state`` (held in HBM, ``pl.ANY``) into a ring of three VMEM
  buffers, updated there, and copied back to the live slot of the SAME
  array (``input_output_aliases``) and, where the pass leaves the row on a
  page boundary, to its snapshot slot. The next block's read and the last
  block's writes run under the current block's arithmetic. A row with
  ``valid == 0`` is neither read nor written; a row without a slot starts
  from zeros and is written nowhere.
- **The recurrence as written, a token at a time**, for as many tokens as
  the row has: ``h <- exp(dt A) h + (dt x) B``; ``y = sum_n C h`` in float32
  on the vector unit, the arithmetic of ``selective_scan_step``. A decode
  lane costs one token whatever the bucket, a chunk row its own tokens. The
  state is walked in chunks of ``LANES`` channels, each through all the
  row's tokens while it sits in registers.
- ``B`` and ``C`` come as columns ``[.., d_state, 1]``: the 16 state
  indices on the sublanes, broadcast over the channels' lanes, as the held
  state ``[d_state, d_inner]`` wants them.
- **The conv tail by row**: the row's new tail is copied to the live and
  snapshot slots of ``conv`` (aliased too) by the row's first block; a slot's
  tail is held as whole tiles of rows of 128
  (``linear_state_pallas.conv_slot_shape``).

Interpret mode on the CPU: tests/test_selective_scan_kernel.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .linear_state_pallas import _rows_of_lanes, _round_up

RING = 3                  # state buffers: read ahead, update, write behind
LANES = 1024              # channels whose state sits in registers at a time
BLOCK_BYTES = 6 << 20     # a block's x, dt and y tiles, at most
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def block_channels(C: int, Sp: int) -> int:
    """Channels a grid step: all of them where the step's token tiles (x,
    dt, y: ``Sp`` rows each) stay within ``BLOCK_BYTES``, else the largest
    divisor of ``C`` in whole lane tiles that does."""
    fits = [c for c in range(128, C + 1, 128)
            if C % c == 0 and 3 * Sp * c * 4 <= BLOCK_BYTES]
    return max(fits, default=min(C, 128))


def _kernel(
    # scalar prefetch
    live_ref,      # [B] flat slot of the row's state (-1: none)
    snap_ref,      # [B] flat slot the new state is also written to (-1: none)
    fresh_ref,     # [B] 1: the state starts from zeros
    valid_ref,     # [B] tokens the row gets (0: the row is left alone)
    # blocks
    x_ref,         # [1, Sp, Cb]
    dt_ref,        # [1, Sp, Cb]
    b_ref,         # [1, Sp, N, 1]
    c_ref,         # [1, Sp, N, 1]
    a_ref,         # [N, Cb]
    tail_ref,      # [1, R, 128]
    state_hbm,     # [slots, N, C] in HBM
    conv_hbm,      # [slots, R, 128] in HBM
    state_out,     # state_hbm again (aliased)
    conv_out,      # conv_hbm again
    y_ref,         # [1, Sp, Cb]
    buf,           # [RING, N, Cb] f32
    rsem,          # DMA [RING]
    wsem,          # DMA [RING, 2 (live, snapshot)]
    tsem,          # DMA [2]
    *,
    n_blocks: int,
    lanes: int,
):
    del conv_hbm
    Cb = buf.shape[2]
    b, cb = pl.program_id(0), pl.program_id(1)
    n = b * n_blocks + cb
    last = pl.num_programs(0) * n_blocks - 1

    def plan(m):
        """(row, first channel, runs, holds a slot) of linear step m."""
        r = m // n_blocks
        return (r, (m - r * n_blocks) * Cb, valid_ref[r] > 0,
                live_ref[r] >= 0)

    def reads(m):
        r, _, runs, held = plan(m)
        return runs & held & (fresh_ref[r] == 0)

    def read(m):
        r, at, _, _ = plan(m)
        return pltpu.make_async_copy(
            state_hbm.at[jnp.maximum(live_ref[r], 0), :, pl.ds(at, Cb)],
            buf.at[m % RING], rsem.at[m % RING])

    def writes(m, which: int):
        r, _, runs, held = plan(m)
        return runs & held & ((snap_ref[r] >= 0) if which else True)

    def write(m, which: int):
        r, at, _, _ = plan(m)
        to = (snap_ref if which else live_ref)[r]
        return pltpu.make_async_copy(
            buf.at[m % RING],
            state_out.at[jnp.maximum(to, 0), :, pl.ds(at, Cb)],
            wsem.at[m % RING, which])

    def drain(m):
        for which in (0, 1):
            @pl.when(writes(m, which))
            def _():
                write(m, which).wait()

    def tail_copy(which: int):
        to = (snap_ref if which else live_ref)[b]
        return pltpu.make_async_copy(
            tail_ref.at[0], conv_out.at[jnp.maximum(to, 0)],
            tsem.at[which])

    def writes_tail(which: int):    # the row's first block carries its tail
        return writes(n, which) & (cb == 0)

    @pl.when((n == 0) & reads(0))
    def _first():
        read(0).start()

    @pl.when(n >= 2)
    def _behind():      # the buffer the next read lands in is step n-2's
        drain(n - 2)

    @pl.when((n < last) & reads(jnp.minimum(n + 1, last)))
    def _ahead():
        read(n + 1).start()

    _, _, runs, _ = plan(n)
    slot = n % RING
    valid = valid_ref[b]
    y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(runs)
    def _run():
        for which in (0, 1):
            @pl.when(writes_tail(which))
            def _():
                tail_copy(which).start()

        @pl.when(reads(n))
        def _():
            read(n).wait()

        @pl.when(jnp.logical_not(reads(n)))
        def _():
            buf[slot] = jnp.zeros(buf.shape[1:], buf.dtype)

        for at in range(0, Cb, lanes):      # the state a chunk at a time
            cols = pl.ds(at, lanes)
            A = a_ref[:, cols]

            def token(t, h, cols=cols, A=A):
                row = pl.ds(t, 1)
                dt = dt_ref[0, row, cols]                       # [1, lanes]
                h = jnp.exp(dt * A) * h + (dt * x_ref[0, row, cols]) * b_ref[0, t]
                y_ref[0, row, cols] = jnp.sum(
                    h * c_ref[0, t], axis=0, keepdims=True)
                return h

            buf[slot, :, cols] = jax.lax.fori_loop(
                0, valid, token, buf[slot, :, cols])

        for which in (0, 1):
            @pl.when(writes(n, which))
            def _():
                write(n, which).start()

            @pl.when(writes_tail(which))
            def _():
                tail_copy(which).wait()

    @pl.when(n == last)
    def _finish():
        @pl.when(n >= 1)
        def _():
            drain(n - 1)
        drain(n)


@functools.lru_cache(maxsize=None)
def _pallas_call(*, B, Sp, C, N, Cb, slots, R, conv_dtype, interpret):
    """The ``pallas_call`` of one kernel shape: ``(live, snap, fresh, valid,
    x, dt, B, C, A, tail, state, conv) -> (state, conv, y)``."""
    n_blocks = C // Cb
    lanes = min(Cb, LANES)
    while Cb % lanes:
        lanes -= 128

    def tokens(width):
        return pl.BlockSpec(
            (1, Sp, width), lambda b, i, *_: (b, 0, i),
            memory_space=pltpu.VMEM)

    columns = pl.BlockSpec(
        (1, Sp, N, 1), lambda b, i, *_: (b, 0, 0, 0), memory_space=pltpu.VMEM)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [
        tokens(Cb), tokens(Cb), columns, columns,
        pl.BlockSpec((N, Cb), lambda b, i, *_: (0, i),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, R, 128), lambda b, i, *_: (b, 0, 0),
                     memory_space=pltpu.VMEM),
        any_space, any_space,
    ]
    state = jax.ShapeDtypeStruct((slots, N, C), jnp.float32)
    conv = jax.ShapeDtypeStruct((slots, R, 128), conv_dtype)
    n_in = 4 + len(in_specs)
    return pl.pallas_call(
        functools.partial(_kernel, n_blocks=n_blocks, lanes=lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, n_blocks),
            in_specs=in_specs,
            out_specs=[any_space, any_space, tokens(Cb)],
            scratch_shapes=[
                pltpu.VMEM((RING, N, Cb), jnp.float32),
                pltpu.SemaphoreType.DMA((RING,)),
                pltpu.SemaphoreType.DMA((RING, 2)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[
            state, conv, jax.ShapeDtypeStruct((B, Sp, C), jnp.float32)],
        input_output_aliases={n_in - 2: 0, n_in - 1: 1},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # the ring carries reads and writes from one step to the next
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        cost_estimate=pl.CostEstimate(
            flops=8 * B * N * C * max(1, Sp // 4),
            bytes_accessed=2 * B * N * C * 4,
            transcendentals=B * N * C * max(1, Sp // 4),
        ),
        name="selective_scan",
    )


def selective_scan_slots(
    x: jax.Array,          # [B, S, C] float32
    dt: jax.Array,         # [B, S, C]
    A: jax.Array,          # [N, C]
    Bm: jax.Array,         # [B, S, N]
    Cm: jax.Array,         # [B, S, N]
    state: jax.Array,      # [slots, N, C] float32
    conv: jax.Array,       # [slots, R, 128]: a slot's tail as rows of 128
    tail: jax.Array,       # [B, W] each row's new conv tail, W <= R * 128
    live: jax.Array,       # [B] int32 flat slot of the row's state (-1: none)
    snap: jax.Array,       # [B] int32 flat slot it is also written to (-1)
    fresh: jax.Array,      # [B] bool: the row's state starts from zeros
    valid: jax.Array,      # [B] int32 tokens of each row
    interpret: bool = False,
):
    """``valid`` tokens of each row through the selective scan, from the
    row's slot of ``state`` and back to it (module header). Returns (y [B,
    S, C], state, conv): the two arrays updated in place where the caller
    donates them."""
    B, S, C = x.shape
    N = A.shape[0]
    if state.dtype != jnp.float32 or state.shape[1:] != (N, C) or C % 128:
        raise ValueError(
            f"pallas-ssm wants a float32 state [slots, {N}, {C}] with the "
            f"channels in whole lane tiles; got "
            f"{state.dtype.name}{tuple(state.shape)}")
    Sp = _round_up(S, 8)

    def padded(a):
        return jnp.pad(a, ((0, 0), (0, Sp - S)) + ((0, 0),) * (a.ndim - 2))

    call = _pallas_call(
        B=B, Sp=Sp, C=C, N=N, Cb=block_channels(C, Sp),
        slots=state.shape[0], R=conv.shape[1], conv_dtype=conv.dtype.name,
        interpret=interpret)
    i32 = jnp.int32
    state, conv, y = call(
        live.astype(i32), snap.astype(i32), fresh.astype(i32),
        valid.astype(i32), padded(x), padded(dt), padded(Bm)[..., None],
        padded(Cm)[..., None], A,
        _rows_of_lanes(tail, conv.shape[1]).astype(conv.dtype), state, conv)
    return y[:, :S], state, conv
