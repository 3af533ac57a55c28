"""Seconds the measured search spent before its first step: the engine's
``search.prepare`` span (identity transforms, the initial fake-quant, the FP
reference forward with taps, the objective's state, the step-0 baseline and
building the step programs; it ends on the baseline's host read)."""

from chipbench import scopes


def read(ctx):
    log = scopes.span_log()
    return None if not log else scopes.prepare_seconds(log)
