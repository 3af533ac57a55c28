"""Share of prompt tokens the batcher served from its prefix cache over the
traced sub-window: ``prefill_tokens_saved`` / (saved + ``prefill_tokens``),
the batcher's own counters."""


def read(ctx):
    saved, done = ctx.get("prefix_saved"), ctx.get("prefill_tokens")
    if saved is None or done is None or saved + done == 0:
        return None
    return 100.0 * saved / (saved + done)
