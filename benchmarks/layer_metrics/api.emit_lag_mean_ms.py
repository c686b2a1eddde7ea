"""Mean lag between the scheduler thread handing a token to the stream and
the HTTP handler passing the chunk that carries it to the socket: the
queue, the executor hop and the event loop, per streamed content chunk.

Layer: OpenAI surface (serving/api.py). Source: the program's histogram
``opsagent_stream_emit_lag_seconds``, ``_sum`` over ``_count`` of the
window's delta. Moves: tpot_p50_ms.
"""
from benchmarks.client import delta

FAMILY = "opsagent_stream_emit_lag_seconds"


def read(ctx: dict):
    n = delta(ctx["before"], ctx["after"], FAMILY + "_count")
    if n <= 0:
        return None
    # cumulative counts by upper bound, for the run's log: a lag of about a
    # tick on part of the chunks is streams waiting for an executor thread
    edges = sorted({ls["le"] for ls, _ in ctx["after"].get(FAMILY + "_bucket", [])},
                   key=float)
    counts = {le: delta(ctx["before"], ctx["after"], FAMILY + "_bucket", le=le)
              for le in edges}
    print(f"[bench] emit lag, chunks at or under each bound (s): {counts}",
          flush=True)
    return delta(ctx["before"], ctx["after"], FAMILY + "_sum") / n * 1e3
