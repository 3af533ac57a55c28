"""Span tracing: wall-time + monotonic-duration events to a JSONL sink.

``trace_span(name, **attrs)`` is a context manager that emits a begin event
(``{"ph": "B", "name", "ts", ...attrs}``) and an end event
(``{"ph": "E", "name", "ts", "dur_s"}``) to the configured sink, measuring
the duration on the MONOTONIC clock. Event ``ts`` values are wall-clock
*valued* but monotonically *derived*: the module captures one
(wall, monotonic) epoch anchor pair at import, every subsequent ``ts`` is
``anchor_wall + (monotonic() - anchor_mono)``, and each sink gets the
anchor written once as a ``{"ph": "M", "name": "clock_anchor"}`` metadata
event. Hosts still line up (via the anchor) but an NTP step mid-run can no
longer reorder or overlap spans within a trace — ``E.ts - B.ts`` is exactly
``dur_s`` by construction. With no sink configured a span still times
itself — callers use
``span.dur`` / ``span.elapsed()`` for metrics — at the cost of two
``perf_counter``-class calls, so instrumenting a hot loop is safe.

Optional integrations:

  hist=       an ``obs.Histogram``; the span observes its duration on exit,
              so "span timing" and "latency histogram" are one call site.
  xprof=True  wraps the body in ``jax.profiler.TraceAnnotation`` (or
              ``StepTraceAnnotation`` when a ``step=`` attr is present), so
              the same spans line up against XLA device activity in a
              ``jax.profiler.trace`` capture. Off by default
              (``enable_xprof()`` flips the process default).

``emit(name, **fields)`` writes a structured instant event (``"ph": "i"``)
and prints a compact ``[name] k=v ...`` line — the replacement for ad-hoc
progress ``print``s in the search engine.

Span ids and parents: every span gets a process-unique ``id``; its begin
event carries it with ``parent``, the id of the innermost span open on the
same thread (None at the top), so a reader can rebuild the span tree.

Compiles: one ``jax.monitoring`` duration listener (``install_compile_
listener``, run once when ``repro.obs`` is imported) counts JAX's traces
and backend compiles (a persistent-cache load counts as a compile) into
``jax_traces_total{fn}``, ``jax_compiles_total{fn}`` and the histogram
``jax_compile_seconds{fn}``. With a sink set, each compile also writes a
``jax.compile`` instant event at its end: ``fn``, ``begin`` (= ts - dur_s),
``dur_s`` and ``parent``, the span open when the compile ran.

``span_log()`` returns the process's last closed spans and compiles
(oldest first; at most ``SPAN_LOG_SIZE``), kept in memory whether or not a
sink is set: ``{"name", "id", "parent", "ts", "dur_s"[, "attrs"]}`` with
``ts`` the begin stamp, and ``{"name": "jax.compile", "fn", "parent",
"ts", "dur_s"}``.
"""
from __future__ import annotations

import collections
import itertools
import json
import threading
import time
from typing import IO, List, Optional, Union

from repro.obs import registry

__all__ = ["trace_span", "emit", "set_trace_sink", "get_trace_sink",
           "trace_to", "enable_xprof", "xprof_enabled", "current_span",
           "span_log", "install_compile_listener"]

_lock = threading.Lock()
_sink: Optional[IO] = None
_sink_owned = False        # opened by us (close on replace) vs caller-owned
_xprof_default = False

# one epoch anchor per process: all event timestamps derive from the
# monotonic clock relative to this pair, so wall-clock adjustments cannot
# shuffle spans within a trace
_EPOCH_WALL = time.time()
_EPOCH_MONO = time.monotonic()

_ids = itertools.count(1)
_local = threading.local()     # .open: ids of this thread's open spans
SPAN_LOG_SIZE = 32768
_span_log: collections.deque = collections.deque(maxlen=SPAN_LOG_SIZE)


def _open_spans() -> list:
    st = getattr(_local, "open", None)
    if st is None:
        st = _local.open = []
    return st


def current_span() -> Optional[int]:
    """Id of the innermost span open on this thread (None if none is)."""
    st = _open_spans()
    return st[-1] if st else None


def span_log() -> List[dict]:
    """The last closed spans and compiles, oldest first (module doc)."""
    return list(_span_log)


def _now_ts() -> float:
    """Wall-valued, monotonically-derived timestamp."""
    return _EPOCH_WALL + (time.monotonic() - _EPOCH_MONO)


def _write_anchor() -> None:
    """Stamp the sink with the epoch anchor (once per installed sink)."""
    _write({"ph": "M", "name": "clock_anchor",
            "wall": _EPOCH_WALL, "mono": _EPOCH_MONO})


def enable_xprof(on: bool = True) -> None:
    """Process default for the ``jax.profiler`` annotation passthrough."""
    global _xprof_default
    _xprof_default = bool(on)


def xprof_enabled() -> bool:
    return _xprof_default


def _open(sink: Union[str, IO, None]):
    """Resolve a sink spec to (file_or_None, owned_by_us)."""
    if isinstance(sink, str):
        import pathlib
        p = pathlib.Path(sink)
        p.parent.mkdir(parents=True, exist_ok=True)
        return p.open("a"), True
    return sink, False


def set_trace_sink(sink: Union[str, IO, None]) -> None:
    """Point span/event output at a JSONL file. A string opens (appends) the
    path; a file-like object is used as-is; ``None`` disables tracing."""
    global _sink, _sink_owned
    new, owned = _open(sink)
    with _lock:
        if _sink is not None and _sink_owned:
            try:
                _sink.close()
            except OSError:
                pass
        _sink, _sink_owned = new, owned
    if new is not None:
        _write_anchor()


def get_trace_sink() -> Optional[IO]:
    return _sink


class trace_to:
    """Scoped sink: ``with trace_to(path): ...`` restores the previous sink
    on exit (tests, nested drivers). The previous sink is left open."""

    def __init__(self, sink: Union[str, IO, None]):
        self._spec = sink

    def __enter__(self):
        global _sink, _sink_owned
        new, owned = _open(self._spec)
        with _lock:
            self._prev, self._prev_owned = _sink, _sink_owned
            _sink, _sink_owned = new, owned
        if new is not None:
            _write_anchor()
        return self

    def __exit__(self, *exc):
        global _sink, _sink_owned
        with _lock:
            if _sink is not None and _sink_owned:
                try:
                    _sink.close()
                except OSError:
                    pass
            _sink, _sink_owned = self._prev, self._prev_owned
        return False


def _write(event: dict) -> None:
    sink = _sink
    if sink is None:
        return
    line = json.dumps(event) + "\n"
    with _lock:
        sink.write(line)
        sink.flush()


class trace_span:
    """Context manager; after exit ``.dur`` holds the monotonic duration in
    seconds. ``elapsed()`` reads the running duration while still open."""

    __slots__ = ("name", "attrs", "hist", "hist_labels", "xprof", "t_wall",
                 "_t0", "dur", "_annotation", "id", "parent")

    def __init__(self, name: str, hist=None, hist_labels: Optional[dict] = None,
                 xprof: Optional[bool] = None, **attrs):
        self.name = name
        self.attrs = attrs
        self.hist = hist
        self.hist_labels = hist_labels or {}
        self.xprof = _xprof_default if xprof is None else xprof
        self.dur: Optional[float] = None
        self._annotation = None

    def elapsed(self) -> float:
        return time.monotonic() - self._t0

    def __enter__(self):
        if self.xprof:
            self._annotation = _make_annotation(self.name, self.attrs)
            if self._annotation is not None:
                self._annotation.__enter__()
        self.id = next(_ids)
        st = _open_spans()
        self.parent = st[-1] if st else None
        st.append(self.id)
        self.t_wall = _now_ts()
        self._t0 = time.monotonic()
        if _sink is not None:
            _write({"ph": "B", "name": self.name, "ts": self.t_wall,
                    "id": self.id, "parent": self.parent,
                    **({"attrs": self.attrs} if self.attrs else {})})
        return self

    def __exit__(self, exc_type, exc, tb):
        self.dur = time.monotonic() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None
        st = _open_spans()
        if st and st[-1] == self.id:
            st.pop()
        elif self.id in st:      # closed out of order: drop it all the same
            st.remove(self.id)
        _span_log.append({"name": self.name, "id": self.id,
                          "parent": self.parent, "ts": self.t_wall,
                          "dur_s": self.dur,
                          **({"attrs": self.attrs} if self.attrs else {})})
        if self.hist is not None:
            self.hist.observe(self.dur, **self.hist_labels)
        if _sink is not None:
            # derived from the begin stamp so E.ts - B.ts == dur_s exactly
            _write({"ph": "E", "name": self.name,
                    "ts": self.t_wall + self.dur,
                    "dur_s": self.dur,
                    **({"error": repr(exc)} if exc is not None else {})})
        return False


def _make_annotation(name: str, attrs: dict):
    """``StepTraceAnnotation`` when a step attribute rides along (XLA step
    markers), plain ``TraceAnnotation`` otherwise; None when the profiler
    API is unavailable (ancient jax)."""
    try:
        from jax import profiler
        if "step" in attrs:
            return profiler.StepTraceAnnotation(name,
                                                step_num=int(attrs["step"]))
        return profiler.TraceAnnotation(name)
    except Exception:  # noqa: BLE001 — tracing must never take the run down
        return None


def emit(name: str, _print: bool = True, **fields) -> str:
    """Structured instant event + compact human line. Returns the line."""
    if _sink is not None:
        _write({"ph": "i", "name": name, "ts": _now_ts(), **fields})
    line = f"[{name}] " + " ".join(f"{k}={v}" for k, v in fields.items())
    if _print:
        print(line, flush=True)
    return line


# ---------------------------------------------------------------------------
# compiles
# ---------------------------------------------------------------------------

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_listening = False


def _on_jax_duration(event: str, duration: float, **kw) -> None:
    if event not in (TRACE_EVENT, COMPILE_EVENT):
        return
    fn = str(kw.get("fun_name", "?"))
    if event == TRACE_EVENT:
        registry.counter("jax_traces_total",
                         "Functions JAX traced to a jaxpr").inc(fn=fn)
        return
    registry.counter(
        "jax_compiles_total",
        "Programs compiled or loaded from the persistent cache").inc(fn=fn)
    registry.histogram(
        "jax_compile_seconds",
        "Backend compile (or cache load) time per program").observe(
            duration, fn=fn)
    end = _now_ts()
    parent = current_span()
    _span_log.append({"name": "jax.compile", "fn": fn, "parent": parent,
                      "ts": end - duration, "dur_s": duration})
    if _sink is not None:
        _write({"ph": "i", "name": "jax.compile", "ts": end,
                "begin": end - duration, "dur_s": duration, "fn": fn,
                "parent": parent})


def install_compile_listener() -> bool:
    """Register the compile listener with ``jax.monitoring`` once per
    process; False where JAX is not importable."""
    global _listening
    if _listening:
        return True
    try:
        from jax import monitoring
    except ImportError:
        return False
    monitoring.register_event_duration_secs_listener(_on_jax_duration)
    _listening = True
    return True
