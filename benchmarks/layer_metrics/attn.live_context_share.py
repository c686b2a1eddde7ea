"""Share of the key slots the attention reader is handed that hold a live context token.

Layer: kernels (the reader of paged keys and values that
``ops.attention.paged_attention_backend`` chose). Source: the window's
deltas of ``opsagent_attn_context_tokens_total``: ``{what="live"}`` (the
context tokens of the rows of every mixed and decode dispatch, counted at
dispatch) over ``{what="read"}`` (the slots the reader is handed: under the
xla gather every row's whole page table, ``max_batch_size x
max_pages_per_seq x 16`` a pass whatever is alive; under the streaming
kernel the live rows' pages). A program without the counter (the parent's)
gives nothing to read. Moves: tpot_p50_ms.
"""
from benchmarks.client import delta

CONTEXT = "opsagent_attn_context_tokens_total"


def read(ctx: dict):
    read_ = delta(ctx["before"], ctx["after"], CONTEXT, what="read")
    if read_ <= 0:
        return None
    live = delta(ctx["before"], ctx["after"], CONTEXT, what="live")
    return 100.0 * live / read_
