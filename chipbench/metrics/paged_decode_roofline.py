"""Paged-decode kernel against its memory roofline: the logical bytes its
calls need (live pages of K and V at their stated dtype without lane
padding, plus queries and outputs; ``counts.paged_decode_bytes``) over the
HBM bandwidth, divided by the kernel's device time in the trace. The kernel
is bandwidth-bound: at one query row per head its operations per byte are
far below the chip's ridge point."""

from chipbench import counts

KERNEL = "paged_decode"


def kernel_seconds(ops: dict) -> float:
    return sum(v for k, v in ops.items() if KERNEL in k)


def read(ctx):
    tr, lens = ctx.get("trace"), ctx.get("decode_lens")
    if not tr or not lens:
        return None
    t = kernel_seconds(tr["ops"])
    if t <= 0:
        return None
    nbytes = sum(counts.paged_decode_bytes(ctx["model"], ls,
                                           ctx["page_size"]) for ls in lens)
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / t
