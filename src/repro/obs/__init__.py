"""Zero-dependency observability: metrics registry, span tracing, compile
counting, device-op scope names and multi-host aggregation. See
``registry``/``tracing``/``programs``/``aggregate`` for the pieces; the
public surface is re-exported here so call sites write ``from repro import
obs`` and stay short. Importing the package registers the compile listener
(``tracing.install_compile_listener``) once per process."""
from repro.obs.registry import (  # noqa: F401
    LATENCY_BUCKETS_S,
    Counter,
    Gauge,
    Histogram,
    Registry,
    counter,
    gauge,
    get_registry,
    hist_quantile,
    histogram,
    merge_snapshots,
    snapshot_json,
)
from repro.obs.tracing import (  # noqa: F401
    current_span,
    emit,
    enable_xprof,
    get_trace_sink,
    install_compile_listener,
    set_trace_sink,
    span_log,
    trace_span,
    trace_to,
    xprof_enabled,
)
from repro.obs.programs import (  # noqa: F401
    program_scopes,
    scope_map,
    scoped,
)
from repro.obs.aggregate import (  # noqa: F401
    DEFAULT_METRICS_PATH,
    dist_snapshot,
    write_snapshot,
)

__all__ = [
    "LATENCY_BUCKETS_S", "Counter", "Gauge", "Histogram", "Registry",
    "counter", "gauge", "get_registry", "hist_quantile", "histogram",
    "merge_snapshots", "snapshot_json",
    "current_span", "emit", "enable_xprof", "get_trace_sink",
    "install_compile_listener", "set_trace_sink", "span_log", "trace_span",
    "trace_to", "xprof_enabled",
    "program_scopes", "scope_map", "scoped",
    "DEFAULT_METRICS_PATH", "dist_snapshot", "write_snapshot",
]

install_compile_listener()
