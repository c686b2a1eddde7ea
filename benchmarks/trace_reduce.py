"""From a ``jax.profiler`` trace (``.xplane.pb``) to numbers.

What it reads: the device planes (``/device:TPU:n``), whose ``XLA Ops`` line
holds one event for each operation that ran on the device, and the host
plane, whose thread lines hold the program's ``TraceAnnotation`` spans
(``engine.mixed_step_async`` and the like) on the same clock.

What it gives: the traced window, the seconds in which an operation ran on
the device (the union of the operation intervals, averaged over the chips),
the time by operation name and by category, the count of each ``engine.*``
annotation, and the idle time by the annotation the host was in.

``reduce_planes`` takes plain tuples, so the arithmetic is tested without a
profile; ``reduce_file`` reads the profile with ``jax.profiler.ProfileData``.
"""

from __future__ import annotations

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
ANNOTATION_PREFIX = "engine."
# The annotations the engine puts around one dispatched step each.
STEP_ANNOTATIONS = (
    "engine.mixed_step", "engine.mixed_step_async", "engine.decode_block",
    "engine.ffwd_step", "engine.prefill_chunk",
)
# The one step annotation that covers several passes of the model: a fused
# decode block is the engine's ``decode_block`` passes in one dispatch.
BLOCK_ANNOTATION = "engine.decode_block"
# Operations that move data into another layout and compute nothing, by
# XLA's own operation name (``%copy.117``, ``%transpose_fusion.3``), or by
# the category the trace gives the event where it gives one.
RELAYOUT_NAMES = ("copy", "transpose")
RELAYOUT_CATEGORY = "data formatting"
TOP = 10
LABEL_CHARS = 96


def read_planes(path: str) -> list:
    """[(plane, [(line, [(name, start_ns, duration_ns, stats)])])] of an
    ``.xplane.pb`` file, or of a gzipped one (the recorded test trace)."""
    import jax

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [
                (e.name, float(e.start_ns), float(e.duration_ns),
                 {k: v for k, v in e.stats if k == "hlo_category"})
                for e in line.events
            ]
            lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes


def _union(intervals: list) -> list:
    """Sorted, merged (start, end) intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return merged


def short_name(name: str) -> str:
    """``%fusion.318 = (f32[4,4]{1,0:T(8,128)}, ...) fusion(...)`` as the
    trace names a device operation -> ``fusion.318 (f32[4,4], ...) fusion``:
    XLA's own name, what it produces and its kind, without the layouts."""
    head, sep, rest = name.partition(" = ")
    head = head.lstrip("%")
    if not sep:
        return head[:LABEL_CHARS]
    out, depth = [], 0
    for ch in rest:
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
        elif depth == 0:
            out.append(ch)
    flat = "".join(out)
    # the result type, then the kind: the word before the operand list
    depth, cut = 0, len(flat)
    for i, ch in enumerate(flat):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == " " and depth == 0:
            cut = i
            break
    kind = flat[cut + 1:].split("(")[0]
    return f"{head} {flat[:cut]} {kind}"[:LABEL_CHARS]


def is_relayout(name: str, category: str = "") -> bool:
    base = name.lstrip("%").split(" ")[0].split(".")[0].split("(")[0]
    return (
        RELAYOUT_CATEGORY in (category or "").lower()
        or any(base == p or base.startswith(p + "-")
               or base.startswith(p + "_") for p in RELAYOUT_NAMES)
    )


def _self_times(events: list) -> list:
    """(name, start, (self_ns, duration_ns), stats) of each event of one
    line. A loop's event spans the events of its body, which lie on the
    same line: an operation's own time is its duration less its children's,
    so that the times add up to the busy time and not to a multiple."""
    rows = sorted(
        ([name, start, [dur, dur], stats]
         for name, start, dur, stats in events if dur > 0),
        key=lambda r: (r[1], -r[2][1]),
    )
    stack: list = []
    for row in rows:
        while stack and stack[-1][1] + stack[-1][2][1] <= row[1]:
            stack.pop()
        if stack:
            stack[-1][2][0] = max(0.0, stack[-1][2][0] - row[2][1])
        stack.append(row)
    return rows


def _label(gap: tuple, spans: list) -> str:
    """The annotation that covers most of the gap, else ``unattributed``."""
    a, b = gap
    best, best_cover = "unattributed", 0.0
    for name, s, e in spans:
        cover = min(b, e) - max(a, s)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce_planes(planes: list, chips: int = 1) -> dict:
    device = [(n, ls) for n, ls in planes if n.startswith(DEVICE_PLANE)]
    device = device[:chips] if chips else device
    spans, t_lo, t_hi = [], float("inf"), float("-inf")
    for name, lines in planes:
        if name.startswith(DEVICE_PLANE):
            continue
        for _line, events in lines:
            for ev, start, dur, _ in events:
                if dur <= 0:
                    continue
                t_lo, t_hi = min(t_lo, start), max(t_hi, start + dur)
                if ev.startswith(ANNOTATION_PREFIX):
                    spans.append((ev, start, start + dur))
    op_s: dict[str, float] = {}
    relayout_ns = busy_ns = op_sum_ns = 0.0
    gaps: dict[str, float] = {}
    busy: list[list] = []
    for _name, lines in device:
        intervals = []
        for line, events in lines:
            if line != OPS_LINE:
                continue
            for ev, start, self_ns, stats in _self_times(events):
                intervals.append((start, start + self_ns[1]))
                label = short_name(ev)
                op_s[label] = op_s.get(label, 0.0) + self_ns[0]
                op_sum_ns += self_ns[0]
                if is_relayout(ev, str(stats.get("hlo_category", ""))):
                    relayout_ns += self_ns[0]
        merged = _union(intervals)
        if merged:
            t_lo = min(t_lo, merged[0][0])
            t_hi = max(t_hi, merged[-1][1])
        busy_ns += sum(b - a for a, b in merged)
        busy.append(merged)
    for merged in busy:
        # idle stretches of this chip, the window's two ends included
        edges = [[t_lo, t_lo], *merged, [t_hi, t_hi]]
        for (_, end), (start, _) in zip(edges, edges[1:]):
            if start > end:
                label = _label((end, start), spans)
                gaps[label] = gaps.get(label, 0.0) + (start - end)
    n = len(device)
    window_ns = max(0.0, t_hi - t_lo) if t_hi > t_lo else 0.0
    counts: dict[str, int] = {}
    for ev, _s, _e in spans:
        counts[ev] = counts.get(ev, 0) + 1

    def top(d: dict, scale: float) -> list:
        rows = sorted(d.items(), key=lambda kv: -kv[1])[:TOP]
        return [[k, v * scale] for k, v in rows]

    per_chip = 1e-9 / n if n else 0.0
    return {
        "devices": n,
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * per_chip,
        "op_sum_s": op_sum_ns * per_chip,
        "relayout_s": relayout_ns * per_chip,
        "annotations": counts,
        "steps": sum(counts.get(name, 0) for name in STEP_ANNOTATIONS),
        "device_ops": top(op_s, per_chip),
        "idle_gaps": top(gaps, per_chip),
    }


def model_passes(annotations: dict, decode_block: int) -> int:
    """Passes of the model that the counted step annotations dispatched."""
    return sum(
        n * (decode_block if name == BLOCK_ANNOTATION else 1)
        for name, n in annotations.items() if name in STEP_ANNOTATIONS
    )


def reduce_file(path: str, chips: int = 1) -> dict:
    return reduce_planes(read_planes(path), chips=chips)
