"""Share of the search's traced window in which the chip ran the routed
experts: the grouped matmuls of the experts held here (``moe.experts``).
Device seconds of the window's operations, named through the program's
compiled scope maps (``chipbench.moe_scopes``), over the window."""

from chipbench import moe_scopes


def read(ctx):
    return moe_scopes.device_share(ctx, ("moe.experts",))
