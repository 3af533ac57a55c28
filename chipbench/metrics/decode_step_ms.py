"""Median of the batcher's ``serve.decode_step`` spans (host clock, the span
ends on the host's read of the step's tokens) in the traced sub-window."""

import statistics


def read(ctx):
    d = ctx.get("decode_spans_s")
    return 1e3 * statistics.median(d) if d else None
