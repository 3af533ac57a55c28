"""Reduction of a profiler trace to device metrics.

A trace is read into plain ``Event`` rows (plane, line, name, start, length
in ns). On a TPU the device planes are ``/device:TPU:<n>``: their ``XLA Ops``
line holds every operation the chip ran, and their ``XLA Modules`` line one
event per program execution, named after the jitted function
(``jit_<name>(<id>)``). Host threads are on ``/host:CPU``, where the
program's spans appear by name once ``obs.enable_xprof()`` is on.

- busy: the union of operation intervals inside the window, averaged over the
  chips used; idle share = 1 - busy / window. Control-flow operations
  (``while``, ``conditional``, ``call``) only contain others and are left
  out, so a loop that waits is not counted busy.
- program and operation time: the summed device durations of the events of
  one name, clipped to the window. An operation is named by its HLO name and
  kind (``%paged_decode.9 custom-call``), a program by its jitted
  function.
- idle gaps: the stretches inside the window where no operation ran, each
  attributed to the innermost host span covering its middle.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PREFIX = "/host:"
SPAN_PREFIXES = ("search.", "serve.", "bench.")   # the spans gaps go to


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def start(trace_dir: str) -> None:
    """Start the profiler: device activity and the host's trace annotations,
    without the Python function tracer (millions of events a minute)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def load_xplane(trace_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


CONTAINERS = ("while", "conditional", "call")
_OP = re.compile(r"^(%\S+) = .*?\s([a-z][a-z0-9\-]*)\(")


def program_name(name: str) -> str:
    """``jit_step(123)`` -> ``jit_step``."""
    return re.sub(r"\(\d+\)$", "", name)


def op_name(name: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...), kind=kLoop`` -> ``%fusion.3
    fusion``; other names unchanged."""
    m = _OP.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def is_container(name: str) -> bool:
    return op_name(name).rsplit(" ", 1)[-1] in CONTAINERS


def device_planes(events: Iterable[Event]) -> List[str]:
    return sorted({e.plane for e in events
                   if e.plane.startswith(DEVICE_PREFIX)})


def _clip(e: Event, lo: float, hi: float) -> Tuple[float, float]:
    return max(e.start_ns, lo), min(e.end_ns, hi)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, non-empty intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_intervals(events: Sequence[Event], plane: str, lo: float, hi: float
                   ) -> List[Tuple[float, float]]:
    return union(_clip(e, lo, hi) for e in events
                 if e.plane == plane and e.line == OPS_LINE
                 and not is_container(e.name))


def time_by_name(events: Sequence[Event], line: str, lo: float, hi: float,
                 key=lambda n: n) -> Dict[str, float]:
    """Device seconds per (keyed) event name on ``line``, clipped to the
    window, summed over the device planes."""
    out: Dict[str, float] = collections.defaultdict(float)
    for e in events:
        if e.line == line and e.plane.startswith(DEVICE_PREFIX):
            a, b = _clip(e, lo, hi)
            if b > a:
                out[key(e.name)] += (b - a) * 1e-9
    return dict(out)


def host_spans(events: Sequence[Event], prefixes: Sequence[str]
               ) -> List[Event]:
    return [e for e in events if e.plane.startswith(HOST_PREFIX)
            and e.name.startswith(tuple(prefixes))]


def span_window(events: Sequence[Event], name: str
                ) -> Optional[Tuple[float, float]]:
    """[start, end] of the first host event called ``name``."""
    for e in sorted(events, key=lambda e: e.start_ns):
        if e.plane.startswith(HOST_PREFIX) and e.name == name:
            return e.start_ns, e.end_ns
    return None


def idle_gaps(busy: Sequence[Tuple[float, float]], spans: Sequence[Event],
              lo: float, hi: float) -> Dict[str, float]:
    """Idle seconds inside [lo, hi] per innermost covering host span name
    ("no span" where none covers the gap's middle)."""
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    out: Dict[str, float] = collections.defaultdict(float)
    for a, b in gaps:
        mid = 0.5 * (a + b)
        cover = [s for s in spans if s.start_ns <= mid <= s.end_ns]
        label = min(cover, key=lambda s: s.dur_ns).name if cover \
            else "no span"
        out[label] += (b - a) * 1e-9
    return dict(out)


def top(d: Dict[str, float], n: int = 10) -> List[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def reduce(events: Sequence[Event], lo: float, hi: float) -> dict:
    """Device metrics of the window [lo, hi] (ns, the trace's clock)."""
    planes = device_planes(events)
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    window = (hi - lo) * 1e-9
    busy_by_plane = {p: busy_intervals(events, p, lo, hi) for p in planes}
    used = [p for p in planes if busy_by_plane[p]]
    if not used:
        raise ValueError("no device operation ran inside the window")
    busy_s = sum(sum(b - a for a, b in busy_by_plane[p]) for p in used) \
        * 1e-9 / len(used)
    spans = host_spans(events, SPAN_PREFIXES)
    gaps: Dict[str, float] = collections.defaultdict(float)
    for p in used:
        for k, v in idle_gaps(busy_by_plane[p], spans, lo, hi).items():
            gaps[k] += v / len(used)
    ops = time_by_name([e for e in events if not is_container(e.name)],
                       OPS_LINE, lo, hi, key=op_name)
    programs = time_by_name(events, MODULES_LINE, lo, hi, key=program_name)
    return {"busy_s": busy_s, "window_s": window,
            "idle_share": 1.0 - busy_s / window if window > 0 else None,
            "device_ops": top({k: v / len(used) for k, v in ops.items()}),
            "idle_gaps": top(dict(gaps)),
            "programs": {k: v / len(used) for k, v in programs.items()},
            "ops": {k: v / len(used) for k, v in ops.items()},
            "n_devices": len(used)}


def summary(events: Sequence[Event], n: int = 25) -> dict:
    """What a hand reading of the trace needs: per plane and line, the event
    count and the names that take most time."""
    by: Dict[Tuple[str, str], Dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    count: Dict[Tuple[str, str], int] = collections.Counter()
    for e in events:
        by[(e.plane, e.line)][e.name] += e.dur_ns * 1e-9
        count[(e.plane, e.line)] += 1
    return {f"{p} | {ln}": {"events": count[(p, ln)],
                            "top_seconds": top(dict(d), n)}
            for (p, ln), d in by.items()}


def extract(events: Sequence[Event], lo: float, hi: float,
            limit: int = 400) -> List[list]:
    """A small recorded piece of a trace: the events that start inside
    [lo, hi] (at most ``limit`` of each plane and line), as plain rows."""
    kept: Dict[Tuple[str, str], int] = collections.Counter()
    out = []
    for e in sorted(events, key=lambda e: e.start_ns):
        if lo <= e.start_ns <= hi and kept[(e.plane, e.line)] < limit:
            kept[(e.plane, e.line)] += 1
            out.append([e.plane, e.line, e.name, e.start_ns, e.dur_ns])
    return out


def write_notes(out_dir, name: str, events: Sequence[Event], lo: float
                ) -> None:
    """For a hand reading: the trace's summary and 5 ms of its events from
    ``lo`` on, as ``<name>.json`` and ``<name>.events.json`` in ``out_dir``."""
    import json
    import pathlib
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{name}.json").write_text(json.dumps(summary(events), indent=1))
    (out / f"{name}.events.json").write_text(
        json.dumps(extract(events, lo, lo + 5e6)))
