"""Device time of the paged prefill-chunk program (``jit_chunk_step`` in the
trace) per prompt token it computed (the batcher's ``prefill_tokens``
counter), over the traced sub-window."""

PROGRAM = "jit_chunk_step"


def read(ctx):
    tr, n = ctx.get("trace"), ctx.get("prefill_tokens")
    if not tr or not n or PROGRAM not in tr["programs"]:
        return None
    return 1e6 * tr["programs"][PROGRAM] / n
