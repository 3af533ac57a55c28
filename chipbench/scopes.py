"""What the program records about itself, for the per-layer readers.

The search job hands its readers the reduced trace (``trace.reduce``), in
which a device operation is named by its HLO instruction and kind
(``%fusion.134 fusion``). The program (``repro.obs``) keeps the rest:

- ``obs.program_scopes()``: for each step program that ran while profiler
  annotations were on, its compiled text's instruction -> ``op_name`` map
  (the ``jax.named_scope`` path). ``seconds_by_layer`` joins the two and
  sums the window's device seconds by model part.
- ``obs.span_log()``: the last closed spans and compiles of the process, on
  the spans' clock. ``prepare_seconds`` and ``compile_seconds_before_window``
  read it.

A program without these records (one that predates them) yields None.
"""
from __future__ import annotations

from typing import Dict, List, Optional

# model part -> scope names that mark it, tried in this order
PARTS = (("attention", ("attn",)),
         ("ffn", ("ffn",)),
         ("lm_head", ("lm_head", "objective.ce")),
         ("hidden_taps", ("objective.act_mse",)))
# the layer scan writes each block's output into the stacked hidden taps at
# the scope around the scan (``blocks``), one level above the block's body;
# where the compiler fuses that write into the FFN's output, the fusion
# carries the FFN's op_name and counts as FFN
TAP_WRITE = ("blocks", "while", "body", "dynamic_update_slice")


def part_of(op_name: str) -> Optional[str]:
    """The model part an ``op_name`` path belongs to (None: another)."""
    parts = op_name.split("/")
    for part, names in PARTS:
        if any(n in parts for n in names):
            return part
    if tuple(parts[-len(TAP_WRITE):]) == TAP_WRITE:
        return "hidden_taps"
    return None


def instruction(op: str) -> str:
    """``%fusion.134 fusion`` -> ``fusion.134``."""
    return op.split(" ", 1)[0].lstrip("%")


def seconds_by_layer(ops: Dict[str, float],
                     maps: Dict[str, Dict[str, str]],
                     program_seconds: Dict[str, float]
                     ) -> Dict[str, float]:
    """Device seconds per model part (and ``other``) of the operations in
    ``ops`` (op name -> seconds). An instruction name that several programs
    share is read from the program that ran longest."""
    names: Dict[str, str] = {}
    for prog in sorted(maps, key=lambda p: program_seconds.get(p, 0.0)):
        names.update(maps[prog])
    out = {part: 0.0 for part, _ in PARTS}
    out["other"] = 0.0
    for op, sec in ops.items():
        op_name = names.get(instruction(op))
        out[(part_of(op_name) if op_name else None) or "other"] += sec
    return out


def program_scopes() -> Optional[Dict[str, Dict[str, str]]]:
    from repro import obs
    fn = getattr(obs, "program_scopes", None)
    return (fn() or None) if fn else None


def device_share(layer: dict, part: str) -> Optional[float]:
    """Percent of the traced window that operations of ``part`` ran."""
    tr, maps = layer.get("trace"), program_scopes()
    if not tr or not maps or not tr.get("window_s"):
        return None
    sec = seconds_by_layer(tr["ops"], maps, tr.get("programs", {}))
    return 100.0 * sec[part] / tr["window_s"]


def span_log() -> Optional[List[dict]]:
    from repro import obs
    fn = getattr(obs, "span_log", None)
    return (fn() or None) if fn else None


def prepare_seconds(log: List[dict]) -> Optional[float]:
    """Duration of the last search's ``search.prepare`` span."""
    prep = [r for r in log if r["name"] == "search.prepare"]
    return prep[-1]["dur_s"] if prep else None


def compile_seconds_before_window(log: List[dict]) -> Optional[float]:
    """Compile (and cache-load) seconds that ended before the measured
    window began: the end of the first ``search.step`` of the last
    ``search.run``."""
    runs = [r for r in log if r["name"] == "search.run"]
    if not runs:
        return None
    steps = [r for r in log if r["name"] == "search.step"
             and r.get("parent") == runs[-1]["id"]]
    if not steps:
        return None
    first = min(steps, key=lambda r: r["ts"])
    start = first["ts"] + first["dur_s"]
    return sum(r["dur_s"] for r in log if r["name"] == "jax.compile"
               and r["ts"] + r["dur_s"] <= start)
