"""The delta rule over state slots, Pallas TPU ("pallas-state").

What a linear-attention layer of a serving step does to its rows'
recurrent state where the code chooses it (``ops.kernels.
linear_state_backend``). Under XLA a row's float32 state crosses HBM some
eight times a layer: copied out of its slot, read three times and written
once by the chunk form at the bucket's width for every row, scattered to
the live slot and again to the snapshot slot, and the conv tail's two
scatters pass over the whole ``conv`` array (PERF.md section 6, PR 37).
This kernel takes the state from read through update to both writes:

- **Once in, once out.** Grid ``(rows, head blocks)``; the flat slot of
  each row, its snapshot target, ``fresh`` and ``valid`` are scalar-
  prefetched. A block of the row's heads is copied from ``state`` (held in
  HBM, ``pl.ANY``) into a ring of three VMEM buffers, updated there, and
  copied back to the live slot of the SAME array (``input_output_aliases``)
  and, where the pass leaves the row on a page boundary, to its snapshot
  slot. The next block's read and the last block's writes run under the
  current block's arithmetic. A row with ``valid == 0`` is neither read
  nor written; a row without a slot starts from zeros and is written
  nowhere.
- **The recurrence as written, a token at a time**, for as many tokens as
  the row has: ``S <- decay S; S <- S + k (v - S^T k)^T; o = S^T q`` in
  float32 on the vector unit, the arithmetic of
  ``linear_attention.delta_rule_step`` (the oracle). A decode lane costs
  one token whatever the bucket, a chunk row its own tokens. No triangular
  system: at a bucket of 16 the chunk form's state-sized matmuls at
  ``highest`` cost the matrix unit more than 16 passes of the vector unit
  over a ``[dk, dv]`` tile cost it, and its pair terms were the larger
  half of ``lin_scan`` under XLA.
- **beta folded into k and v**: ``k (beta (v - S^T k))^T = k' (v' - S^T
  k')^T`` with ``k' = sqrt(beta) k``, ``v' = sqrt(beta) v`` (``beta`` is a
  sigmoid's, never negative), so no scalar rides beside a token.
- **Both decay forms**, read from the shape of ``g``: a decay a channel is
  a third column beside q and k; a decay a head rides in lane ``dk`` of
  the k tile.
- **Two heads side by side** where one head's value dim is off the 128
  lanes and two are on them (``dv = 192``): the slot is held ``[H / 2, dk,
  2 dv]`` (``llama.state_slot_shape``), a token's columns are picked by
  lane, and v and o are the same bytes as ``[.., H, dv]``.
- **The conv tail by row**: the row's new tail is copied to the live and
  snapshot slots of ``conv`` (aliased too) by the row's first block.

Exported once a shape (``pallas_export``), as the streaming attention
kernel is. Interpret mode on the CPU: tests/test_linear_state_kernel.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_export import exported_call

RING = 3                  # state buffers: read ahead, update, write behind
TOKEN_GROUP = 16          # tokens transposed to columns at a time
BLOCK_BYTES = 1 << 20     # a block of heads' state, at most
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def heads_packed(dv: int) -> int:
    """Heads side by side in a held state tile: 1 where a head's value dim
    fills whole 128-lane tiles, 2 where two heads' do."""
    return 1 if dv % 128 == 0 else 2


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def conv_slot_shape(width: int) -> tuple[int, int]:
    """A slot's conv tail of ``width`` elements as the kernel holds it:
    rows of 128 lanes, as many as whole 16-row tiles of a 2-byte type hold
    (a slot is then a leading index of ``conv``, which a DMA can take; one
    row of a ``[slots, width]`` array is a slice inside a tile, which it
    cannot)."""
    return _round_up(-(-width // 128), 16), 128


def _rows_of_lanes(tail: jax.Array, rows: int) -> jax.Array:
    """[B, width] -> [B, rows, 128], zero-padded."""
    B, width = tail.shape
    return jnp.pad(tail, ((0, 0), (0, rows * 128 - width))).reshape(
        B, rows, 128)


def _kernel(
    # scalar prefetch
    live_ref,      # [B] flat slot of the row's state (-1: none)
    snap_ref,      # [B] flat slot the new state is also written to (-1: none)
    fresh_ref,     # [B] 1: the state starts from zeros
    valid_ref,     # [B] tokens the row gets (0: the row is left alone)
    # blocks
    q_ref,         # [1, Hb, Sp, dk]
    k_ref,         # [1, Hb, Sp, dk (+ the decay a head's lane, padded)]
    *rest,
    n_blocks: int,
    packed: int,
    dk: int,
    dv: int,
    by_channel: bool,
    tg: int,
):
    if by_channel:
        g_ref, *rest = rest    # [1, Hb, Sp, dk]: exp(g)
    (v_ref,        # [1, G, Sp, packed * dv]
     tail_ref,     # [1, R, 128]
     state_hbm,    # [slots, H / packed, dk, packed * dv] in HBM
     conv_hbm,     # [slots, R, 128] in HBM
     state_out,    # state_hbm again (aliased)
     conv_out,     # conv_hbm again
     o_ref,        # [1, G, Sp, packed * dv]
     buf,          # [RING, G, dk, packed * dv] f32
     rsem,         # DMA [RING]
     wsem,         # DMA [RING, 2 (live, snapshot)]
     tsem,         # DMA [2]
     ) = rest
    del conv_hbm
    G = buf.shape[1]
    b, hb = pl.program_id(0), pl.program_id(1)
    n = b * n_blocks + hb
    last = pl.num_programs(0) * n_blocks - 1

    def plan(m):
        """(row, first head group, runs, holds a slot) of linear step m."""
        r = m // n_blocks
        return (r, (m - r * n_blocks) * G, valid_ref[r] > 0,
                live_ref[r] >= 0)

    def reads(m):
        r, _, runs, held = plan(m)
        return runs & held & (fresh_ref[r] == 0)

    def read(m):
        r, at, _, _ = plan(m)
        return pltpu.make_async_copy(
            state_hbm.at[jnp.maximum(live_ref[r], 0), pl.ds(at, G)],
            buf.at[m % RING], rsem.at[m % RING])

    def writes(m, which: int):
        r, _, runs, held = plan(m)
        return runs & held & ((snap_ref[r] >= 0) if which else True)

    def write(m, which: int):
        r, at, _, _ = plan(m)
        to = (snap_ref if which else live_ref)[r]
        return pltpu.make_async_copy(
            buf.at[m % RING],
            state_out.at[jnp.maximum(to, 0), pl.ds(at, G)],
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
        return writes(n, which) & (hb == 0)

    @pl.when((n == 0) & reads(0))
    def _first():
        read(0).start()

    @pl.when(n >= 2)
    def _behind():      # the buffer the next read lands in is step n-2's
        drain(n - 2)

    @pl.when((n < last) & reads(jnp.minimum(n + 1, last)))
    def _ahead():
        read(n + 1).start()

    _, _, runs, held = plan(n)
    slot = n % RING
    valid = valid_ref[b]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, packed * dv), 1)

    def by_lane(parts):
        """One value a head of the tile -> what its lanes see."""
        if packed == 1:
            return parts[0]
        return jnp.where(lane < dv, parts[0], parts[1])

    def group(gi, _):
        state = buf.at[slot, gi]                    # [dk, packed * dv]

        def tokens(ti, _):
            base = pl.multiple_of(ti * tg, tg)
            rows = pl.ds(base, tg)
            heads = [gi * packed + p for p in range(packed)]
            # tokens to lanes: a token's q, k, decay are columns of these
            qT = [q_ref[0, h, rows, :].T for h in heads]    # [dk, tg]
            kT = [k_ref[0, h, rows, :].T for h in heads]
            gT = [g_ref[0, h, rows, :].T for h in heads] if by_channel else kT

            def token(j: int, S):
                if by_channel:
                    decay = by_lane([t[:dk, j:j + 1] for t in gT])
                else:
                    decay = by_lane([t[dk:dk + 1, j:j + 1] for t in gT])
                k = by_lane([t[:dk, j:j + 1] for t in kT])
                q = by_lane([t[:dk, j:j + 1] for t in qT])
                S = S * decay
                at = pl.ds(base + j, 1)
                u = v_ref[0, gi, at, :] - jnp.sum(S * k, axis=0, keepdims=True)
                S = S + k * u
                o_ref[0, gi, at, :] = jnp.sum(S * q, axis=0, keepdims=True)
                return S

            @pl.when(base + tg <= valid)
            def _whole():       # the state stays in registers
                S = state[...]
                for j in range(tg):
                    S = token(j, S)
                state[...] = S

            @pl.when(base + tg > valid)
            def _ragged():
                for j in range(tg):
                    @pl.when(base + j < valid)
                    def _():
                        state[...] = token(j, state[...])

        jax.lax.fori_loop(0, pl.cdiv(valid, tg), tokens, None)

    o_ref[...] = jnp.zeros_like(o_ref)

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

        jax.lax.fori_loop(0, G, group, None)
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


def _pallas_call(
    *, B, H, Sp, dk, dkx, dv, packed, by_channel, Hb, slots, R, conv_dtype,
    interpret,
):
    """The ``pallas_call`` of one kernel shape: ``(live, snap, fresh, valid,
    q, k[, decay], v, tail, state, conv) -> (state, conv, o)``."""
    G = Hb // packed
    n_blocks = H // Hb
    lanes = packed * dv

    def heads(width):
        return pl.BlockSpec(
            (1, Hb, Sp, width), lambda b, i, *_: (b, i, 0, 0),
            memory_space=pltpu.VMEM)

    values = pl.BlockSpec(
        (1, G, Sp, lanes), lambda b, i, *_: (b, i, 0, 0),
        memory_space=pltpu.VMEM)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [heads(dk), heads(dkx)]
    if by_channel:
        in_specs.append(heads(dk))
    in_specs += [
        values,
        pl.BlockSpec((1, R, 128), lambda b, i, *_: (b, 0, 0),
                     memory_space=pltpu.VMEM),
        any_space, any_space,
    ]
    state = jax.ShapeDtypeStruct((slots, H // packed, dk, lanes), jnp.float32)
    conv = jax.ShapeDtypeStruct((slots, R, 128), conv_dtype)
    n_in = 4 + len(in_specs)
    return pl.pallas_call(
        functools.partial(
            _kernel, n_blocks=n_blocks, packed=packed, dk=dk, dv=dv,
            by_channel=by_channel, tg=min(Sp, TOKEN_GROUP),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, n_blocks),
            in_specs=in_specs,
            out_specs=[any_space, any_space, values],
            scratch_shapes=[
                pltpu.VMEM((RING, G, dk, lanes), jnp.float32),
                pltpu.SemaphoreType.DMA((RING,)),
                pltpu.SemaphoreType.DMA((RING, 2)),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=[
            state, conv,
            jax.ShapeDtypeStruct((B, H // packed, Sp, lanes), jnp.float32),
        ],
        input_output_aliases={n_in - 2: 0, n_in - 1: 1},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # the ring carries reads and writes from one step to the next
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        cost_estimate=pl.CostEstimate(
            flops=8 * B * H * dk * dv * max(1, Sp // 4),
            bytes_accessed=2 * B * H * dk * dv * 4,
            transcendentals=0,
        ),
        name="linear_state",
    )


@functools.lru_cache(maxsize=None)
def _kernel_call(*, inline: bool, **shape):
    """The kernel of one shape as something to call inside a step program:
    traced and lowered once, then inlined as bytes (``pallas_export``)."""
    call = _pallas_call(**shape)
    if inline:
        return call
    B, H, Sp, packed = (shape[k] for k in ("B", "H", "Sp", "packed"))
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    lanes = packed * shape["dv"]
    args = [i32((B,))] * 4 + [
        f32((B, H, Sp, shape["dk"])), f32((B, H, Sp, shape["dkx"]))]
    if shape["by_channel"]:
        args.append(f32((B, H, Sp, shape["dk"])))
    args += [
        f32((B, H // packed, Sp, lanes)),
        jax.ShapeDtypeStruct((B, shape["R"], 128), shape["conv_dtype"]),
        f32((shape["slots"], H // packed, shape["dk"], lanes)),
        jax.ShapeDtypeStruct(
            (shape["slots"], shape["R"], 128), shape["conv_dtype"]),
    ]
    return exported_call(
        call, args, name="linear_state", source=__file__, shape=shape,
        scope="lin_scan")


def block_heads(H: int, packed: int, dk: int, dv: int, Sp: int) -> int:
    """Heads a grid step: the most that divide ``H`` in whole packed groups
    with their state within ``BLOCK_BYTES`` and their tokens' tiles (q, k,
    decay, v, o) within four times that."""
    state, tokens = dk * dv * 4, Sp * (3 * dk + 2 * dv) * 4
    fits = [n for n in range(packed, H + 1, packed)
            if H % n == 0 and n * state <= BLOCK_BYTES
            and n * tokens <= 4 * BLOCK_BYTES]
    return max(fits, default=packed)


def delta_rule_slots(
    q: jax.Array,          # [B, S, H, dk] float32
    k: jax.Array,          # [B, S, H, dk]
    v: jax.Array,          # [B, S, H, dv]
    g: jax.Array,          # [B, S, H, dk] or [B, S, H]
    beta: jax.Array,       # [B, S, H]
    state: jax.Array,      # [slots, H / packed, dk, packed * dv] float32
    conv: jax.Array,       # [slots, R, 128]: a slot's tail as rows of 128
    tail: jax.Array,       # [B, W] each row's new conv tail, W <= R * 128
    live: jax.Array,       # [B] int32 flat slot of the row's state (-1: none)
    snap: jax.Array,       # [B] int32 flat slot it is also written to (-1)
    fresh: jax.Array,      # [B] bool: the row's state starts from zeros
    valid: jax.Array,      # [B] int32 tokens of each row
    interpret: bool = False,
):
    """``valid`` tokens of each row through the delta rule, from the row's
    slot of ``state`` and back to it (module header). Returns (o [B, S, H,
    dv], state, conv): the two arrays updated in place where the caller
    donates them."""
    B, S, H, dk = k.shape
    dv = v.shape[-1]
    packed = H // max(1, state.shape[1])
    if state.dtype != jnp.float32 or state.shape[1:] != (
            H // packed, dk, packed * dv) or packed not in (1, 2):
        raise ValueError(
            f"pallas-state wants a float32 state [slots, H / p, dk, p * dv] "
            f"with p = 1 or 2; got {state.dtype.name}{tuple(state.shape)} "
            f"for H={H}, dk={dk}, dv={dv}")
    by_channel = g.ndim == k.ndim
    Sp = _round_up(S, 8 if S <= 8 else TOKEN_GROUP)
    root = jnp.sqrt(beta)[..., None]
    kx = k * root
    if not by_channel:      # the decay a head rides beside the key
        dkx = _round_up(dk + 1, 128)
        kx = jnp.concatenate([
            kx, jnp.exp(g)[..., None],
            jnp.zeros((B, S, H, dkx - dk - 1), kx.dtype)], axis=-1)

    def by_head(a):         # [B, S, H, x] -> [B, H, Sp, x]
        a = jnp.pad(a, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        return a.transpose(0, 2, 1, 3)

    lanes = packed * dv
    values = by_head(v * root).reshape(B, H // packed, packed, Sp, dv)
    values = values.transpose(0, 1, 3, 2, 4).reshape(B, H // packed, Sp, lanes)
    args = [by_head(q), by_head(kx)]
    if by_channel:
        args.append(by_head(jnp.exp(g)))
    call = _kernel_call(
        B=B, H=H, Sp=Sp, dk=dk, dkx=kx.shape[-1], dv=dv, packed=packed,
        by_channel=by_channel, Hb=block_heads(H, packed, dk, dv, Sp),
        slots=state.shape[0], R=conv.shape[1], conv_dtype=conv.dtype.name,
        interpret=interpret,
        inline=interpret or bool(jax.sharding.get_abstract_mesh().manual_axes),
    )
    i32 = jnp.int32
    state, conv, o = call(
        live.astype(i32), snap.astype(i32), fresh.astype(i32),
        valid.astype(i32), *args, values,
        _rows_of_lanes(tail, conv.shape[1]).astype(conv.dtype), state, conv)
    o = o.reshape(B, H // packed, Sp, packed, dv).transpose(0, 2, 1, 3, 4)
    return o.reshape(B, Sp, H, dv)[:, :S], state, conv
