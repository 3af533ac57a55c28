"""Share of the search's traced window in which the chip ran attention: the
operations under the ``attn`` scope.
Device seconds of the window's operations, named through the program's
compiled scope maps (``chipbench.scopes``), over the window."""

from chipbench import scopes


def read(ctx):
    return scopes.device_share(ctx, "attention")
