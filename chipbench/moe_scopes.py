"""Device seconds of the MoE layer's parts, read from the program's compiled
scope maps as ``scopes.device_share`` reads the four model parts.

The program names its MoE work ``moe.router``, ``moe.dispatch`` (sort,
gather, scatter, combine), ``moe.experts`` (the grouped matmuls) and
``moe.shared``; none lies under ``attn``, ``ffn``, ``lm_head`` or the
objective's scopes, so these shares come out of ``scopes``'s "other" and
add to the four parts without overlap. A program without these scopes reads
0; one without scope maps reads None.
"""
from __future__ import annotations

from typing import Optional, Sequence

from chipbench import scopes


def device_share(layer: dict, names: Sequence[str]) -> Optional[float]:
    """Percent of the traced window that operations under any of the scopes
    ``names`` ran (an instruction name several programs share is read from
    the program that ran longest, as ``scopes.seconds_by_layer`` does)."""
    tr, maps = layer.get("trace"), scopes.program_scopes()
    if not tr or not maps or not tr.get("window_s"):
        return None
    progs = tr.get("programs", {})
    op_names = {}
    for prog in sorted(maps, key=lambda p: progs.get(p, 0.0)):
        op_names.update(maps[prog])
    sec = 0.0
    for op, s in tr["ops"].items():
        name = op_names.get(scopes.instruction(op))
        if name and scopes.part_of(name) is None and \
                any(n in name.split("/") for n in names):
            sec += s
    return 100.0 * sec / tr["window_s"]
