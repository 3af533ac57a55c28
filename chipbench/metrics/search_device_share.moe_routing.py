"""Share of the search's traced window in which the chip ran the MoE routing
and dispatch: the router's scores and top-k (``moe.router``) and the sort,
gather, scatter and combine of the token slots (``moe.dispatch``).
Device seconds of the window's operations, named through the program's
compiled scope maps (``chipbench.moe_scopes``), over the window."""

from chipbench import moe_scopes


def read(ctx):
    return moe_scopes.device_share(ctx, ("moe.router", "moe.dispatch"))
