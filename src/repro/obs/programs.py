"""Scope names for a profile's device operations.

A device trace names each operation by its HLO instruction
(``%fusion.134``); the part of the model it belongs to is the instruction's
``op_name`` metadata, the ``jax.named_scope`` path it was traced under
(``jit(eval_body)/while/body/blocks/while/body/attn/dot_general``). That
path lives in the compiled program, not in the trace, so while profiler
annotations are on (``enable_xprof``) a program wrapped by ``scoped`` records
its compiled text's instruction -> ``op_name`` map after its first call.
``program_scopes()`` returns the maps recorded so far, by program name.

Recording costs one lowering of the wrapped function for the same
arguments, which JAX answers from its in-memory caches (no compile), plus
reading the program's text; with annotations off the wrapper only checks a
flag.
"""
from __future__ import annotations

import re
from typing import Callable, Dict

from repro.obs import tracing

__all__ = ["scope_map", "scoped", "program_scopes"]

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%(\S+) = .*?metadata=\{op_name="([^"]*)"',
                    re.M)
_programs: Dict[str, Dict[str, str]] = {}


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction name (without ``%``) -> ``op_name`` for every instruction
    of an HLO module's text that carries one."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(hlo_text)}


def program_scopes() -> Dict[str, Dict[str, str]]:
    """Program name (``jit_<function>``) -> its instruction -> ``op_name``
    map, for every program recorded since the process started."""
    return dict(_programs)


def scoped(jitted: Callable) -> Callable:
    """``jitted`` (a ``jax.jit`` function) called as before; its first call
    while ``enable_xprof`` is on records the compiled program's scope map
    under ``jit_<function name>``, the name the trace gives the program."""
    name = jitted.__name__
    noted = False

    def call(*args):
        nonlocal noted
        out = jitted(*args)
        if not noted and tracing.xprof_enabled():
            noted = True
            text = jitted.lower(*args).compile().as_text()
            _programs[f"jit_{name}"] = scope_map(text)
        return out

    return call
