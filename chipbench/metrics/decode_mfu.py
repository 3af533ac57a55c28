"""Share of the chip's peak that decoding reaches: each decode step's model
operations (the matmuls for every live sequence, and attention over each
one's context) over the summed ``serve.decode_step`` span time and the bf16
peak, in the traced sub-window."""

from chipbench import counts


def read(ctx):
    lens, spans = ctx.get("decode_lens"), ctx.get("decode_spans_s")
    if not lens or not spans:
        return None
    flops = sum(counts.decode_step_flops(ctx["model"], ls) for ls in lens)
    return 100.0 * flops / (sum(spans) * ctx["peaks"]["bf16_flops_per_s"])
