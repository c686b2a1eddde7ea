"""From a ``jax.profiler`` trace to device time by the program's own names.

``trace_reduce`` reads a trace through ``jax.profiler.ProfileData``, which
hands out the stats of an event and not those of its metadata. The names
the program gives its kernels live in the metadata: every operation on a
device plane's ``XLA Ops`` line has, as stats of its ``XEventMetadata``,
``tf_op`` (the JAX ``op_name`` path, ``jit(_mixed_carry)/while/body/
attn_core/kv_gather/gather``), ``hlo_category``, ``flops``,
``bytes_accessed``, ``source`` and ``program_id``. So this module reads the
``.xplane.pb`` wire format itself (four message types, no dependency) and
gives:

- ``scope_s``: the device's operation time by ``jax.named_scope`` name. An
  operation's own time (``trace_reduce._self_times``: a ``while`` less its
  body) goes to the innermost name on its ``tf_op`` path (the first path,
  where XLA joined several with ``;``) that is in ``SCOPES`` or among the
  names the cell's family adds (``families/<family>.py`` ``SCOPES``), else
  to ``unscoped``; seconds per chip, which add up to ``trace_reduce``'s
  ``op_sum_s``. A name a family adds takes its time from the scope that
  encloses it, and the total does not change.
- ``unscoped_ops``: the largest operations left in ``unscoped``.
- ``module_s``: the mean device duration of each executed program on the
  ``XLA Modules`` line (``jit__mixed_carry``), and ``module_n`` their count,
  less the line's first and last event, which the capture's ends cut short.

The ``kernels.*_ms`` readers under ``layer_metrics/`` divide ``scope_s`` by
the model passes of the traced span. A program without the scopes (the
parent of the PR that added them) reads everything as ``unscoped``; a run
without a device plane (a CPU rehearsal) reads ``None``.
"""

from __future__ import annotations

import functools
import glob
import gzip
import json
import os
import struct

from benchmarks import trace_reduce
from benchmarks.loading import load_family

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The program's scope vocabulary (opsagent_tpu/models/llama.py SCOPES): the
# names every family's step has. A family whose program names more (a
# router, a latent up-projection) lists them in its module.
SCOPES = (
    "embed", "attn_qkv", "kv_write", "kv_gather", "attn_core", "attn_out",
    "ffn", "lm_head", "sample",
)
UNSCOPED = "unscoped"
MODULES_LINE = "XLA Modules"
TOP = 8


# -- protobuf wire format ------------------------------------------------------
def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes):
    """(field number, wire type, value) of one message: an int for varint
    and fixed fields, a memoryview slice for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif kind == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield number, kind, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _stat(buf: bytes, stat_names: dict) -> tuple[str, object]:
    """One XStat -> (name, value). A ``ref_value`` names a stat metadata
    entry whose name is the string."""
    name, value = "", None
    for number, kind, v in _fields(buf):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:       # double, as fixed64
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif number in (3, 4):
            value = v
        elif number in (5, 6):
            value = _text(v)
        elif number == 7:
            value = stat_names.get(v, "")
    return name, value


def _map_entry(buf: bytes):
    key, value = 0, b""
    for number, _kind, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _plane(buf: bytes) -> tuple[str, list]:
    """One XPlane -> (name, [(line name, [(event name, start_ns,
    duration_ns, metadata stats)])])."""
    name, raw_lines, raw_meta, stat_names = "", [], [], {}
    for number, _kind, v in _fields(buf):
        if number == 2:
            name = _text(v)
        elif number == 3:
            raw_lines.append(v)
        elif number == 4:
            raw_meta.append(v)
        elif number == 5:
            key, value = _map_entry(v)
            for n, _k, x in _fields(value):
                if n == 2:
                    stat_names[key] = _text(x)
    meta: dict[int, tuple[str, dict]] = {}
    for entry in raw_meta:
        key, value = _map_entry(entry)
        ev_name, stats = "", {}
        for n, _k, x in _fields(value):
            if n == 2:
                ev_name = _text(x)
            elif n == 5:
                stat, stat_value = _stat(x, stat_names)
                stats[stat] = stat_value
        meta[key] = (ev_name, stats)
    lines = []
    for raw in raw_lines:
        line_name, t0_ns, raw_events = "", 0, []
        for n, _k, x in _fields(raw):
            if n == 2:
                line_name = _text(x)
            elif n == 3:
                t0_ns = x
            elif n == 4:
                raw_events.append(x)
        events = []
        for ev in raw_events:
            mid = offset_ps = duration_ps = 0
            for n, _k, x in _fields(ev):
                if n == 1:
                    mid = x
                elif n == 2:
                    offset_ps = x
                elif n == 3:
                    duration_ps = x
            ev_name, stats = meta.get(mid, (str(mid), {}))
            events.append((ev_name, t0_ns + offset_ps * 1e-3,
                           duration_ps * 1e-3, stats))
        lines.append((line_name, events))
    return name, lines


def read_planes(path: str) -> list:
    """``trace_reduce.read_planes``'s shape, with the stats of each event's
    metadata in the place of the event's own."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = memoryview(f.read())
    return [_plane(v) for number, _k, v in _fields(data) if number == 1]


# -- the reduction -------------------------------------------------------------
def scope_of(tf_op: str, scopes: tuple = SCOPES) -> str:
    """The innermost name of ``scopes`` on an ``op_name`` path. Where XLA has
    merged operations it joins their paths with ``;`` (the whole-cache copy
    between the page write's flat view and the gather's paged one reads
    ``.../kv_gather/reshape;kv_write/kv_write/reshape``): the first path
    counts, which charges that re-tiling to ``kv_gather``, as the
    vocabulary has it."""
    first = str(tf_op or "").split(";")[0]
    for part in reversed(first.split("/")):
        if part in scopes:
            return part
    return UNSCOPED


def reduce_planes(planes: list, chips: int = 1, extra: tuple = ()) -> dict:
    """``extra``: the scope names the cell's family adds to ``SCOPES``."""
    device = [(n, ls) for n, ls in planes
              if n.startswith(trace_reduce.DEVICE_PLANE)]
    device = device[:chips] if chips else device
    scopes = (*SCOPES, *(s for s in extra if s not in SCOPES))
    scope_ns = dict.fromkeys((*scopes, UNSCOPED), 0.0)
    unscoped_ns: dict[str, float] = {}
    module_ns: dict[str, list] = {}
    with_tf_op = ops = 0
    for _name, lines in device:
        for line, events in lines:
            if line == trace_reduce.OPS_LINE:
                for ev, _start, self_ns, stats in trace_reduce._self_times(events):
                    ops += 1
                    with_tf_op += "tf_op" in stats
                    scope = scope_of(stats.get("tf_op", ""), scopes)
                    scope_ns[scope] += self_ns[0]
                    if scope == UNSCOPED:
                        label = trace_reduce.short_name(ev)
                        unscoped_ns[label] = (
                            unscoped_ns.get(label, 0.0) + self_ns[0])
            elif line == MODULES_LINE:
                # The capture's two ends cut the executions they fall in:
                # the line's first and last event are left out.
                whole = sorted(events, key=lambda e: e[1])
                whole = whole[1:-1] if len(whole) > 2 else whole
                for ev, _start, dur, _stats in whole:
                    program = ev.split("(")[0]
                    module_ns.setdefault(program, []).append(dur)
    n = len(device)
    per_chip = 1e-9 / n if n else 0.0
    top = sorted(unscoped_ns.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "devices": n,
        "ops": ops,
        "ops_with_tf_op": with_tf_op,
        "scope_s": {k: v * per_chip for k, v in scope_ns.items()},
        "unscoped_ops": [[k, v * per_chip] for k, v in top],
        "module_s": {k: sum(v) / len(v) * 1e-9 for k, v in module_ns.items()},
        "module_n": {k: len(v) for k, v in module_ns.items()},
    }


@functools.lru_cache(maxsize=2)
def _reduce_cached(path: str, mtime: float, chips: int, extra: tuple) -> dict:
    """One reduction per capture, shared by the readers of a run; its
    summary goes to the run's log once (not into the result line)."""
    got = reduce_planes(read_planes(path), chips=chips, extra=extra)
    print(f"[bench] scope_reduce {os.path.relpath(path, ROOT)}: "
          + json.dumps(got), flush=True)
    return got


def reduce_file(path: str, chips: int = 1, extra: tuple = ()) -> dict:
    return _reduce_cached(path, os.path.getmtime(path), chips, tuple(extra))


def newest_trace() -> str | None:
    """The newest capture a run of ``run.py`` left behind (the readers run
    after the server has gone, and their ``ctx`` carries no path)."""
    found = glob.glob(
        os.path.join(ROOT, ".bench_out", "*", "trace", "**", "*.xplane.pb"),
        recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def for_run(ctx: dict) -> dict | None:
    """The reduction of this run's capture, or None where it has no device
    plane (a CPU rehearsal) or no capture at all."""
    trace = ctx.get("trace")
    if not trace or not trace.get("devices"):
        return None
    path = newest_trace()
    if path is None:
        return None
    config = ctx.get("config") or {}
    extra = load_family(config).SCOPES if "family" in config else ()
    got = reduce_file(path, chips=trace["devices"], extra=extra)
    return got if got["devices"] else None


def scope_ms_per_pass(ctx: dict, *scopes: str) -> float | None:
    """Milliseconds of device operation time a model pass spent under the
    given scopes in the traced span (``trace_reduce.model_passes``: a
    fused decode block counts its ``decode_block`` passes). A scope is one
    of ``SCOPES``, ``unscoped`` or a name the cell's family adds."""
    got = for_run(ctx)
    if got is None:
        return None
    passes = trace_reduce.model_passes(
        ctx["trace"]["annotations"], ctx["config"]["engine"]["decode_block"])
    if not passes:
        return None
    return sum(got["scope_s"][s] for s in scopes) / passes * 1e3
