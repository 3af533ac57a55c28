"""Share of the search's traced window in which the chip ran the activation
taps: the activation MSE over the stacked hidden taps (``objective.act_mse``)
and any stand-alone write of the layer scan's stacked output
(``blocks/while/body/dynamic_update_slice``). On the TPU v5e the compiler
fuses that write into the FFN down projection's output fusion
(``%bitcast_dynamic-update-slice_fusion.N``), which carries the matmul's
``.../ffn/dot_general`` and is counted with the FFN.
Device seconds of the window's operations, named through the program's
compiled scope maps (``chipbench.scopes``), over the window."""

from chipbench import scopes


def read(ctx):
    return scopes.device_share(ctx, "hidden_taps")
