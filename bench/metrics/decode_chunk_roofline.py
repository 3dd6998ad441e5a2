"""Share of its roofline that the decode chunk program reaches: the least
time of the traced window's decode ticks (each the larger of its FLOPs
over the bf16 peak and its least bytes over HBM bandwidth: every weight
but the embedding, the resident KV read, the KV written) over the device
time of the chunk programs (`jit_chunk`) in the trace, percent."""
from bench import trace_reduce


def read(ctx):
    s = ctx.get("trace")
    least = ctx.get("decode_least_s")
    if not s or not least:
        return None
    dev = trace_reduce.module_seconds(s, "jit_chunk")
    if not dev:
        return None
    return 100.0 * least / dev
