"""Share of the search's traced window in which the chip ran the LM head and
the cross-entropy over its logits: the operations under the ``lm_head``
and ``objective.ce`` scopes.
Device seconds of the window's operations, named through the program's
compiled scope maps (``chipbench.scopes``), over the window."""

from chipbench import scopes


def read(ctx):
    return scopes.device_share(ctx, "lm_head")
