"""Seconds of set-up spent compiling or loading programs: the program's
``jax.compile`` records (``jax_compile_seconds``) from process start to the
end of the measured search's first step, where the window starts."""

from chipbench import scopes


def read(ctx):
    log = scopes.span_log()
    return None if not log else scopes.compile_seconds_before_window(log)
