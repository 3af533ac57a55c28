"""The program's spans, captured in memory on the host's monotonic clock.

``repro.obs`` writes one JSON line per span begin and end to whatever sink it
is given. ``SpanSink`` is such a sink: it keeps every event with the
monotonic time of its arrival and a snapshot of the compile counter, so a
window bounded by two spans can also say how many programs compiled in it.
"""
from __future__ import annotations

import io
import json
import time
from typing import Callable, List, Optional, Tuple


class SpanSink(io.TextIOBase):
    def __init__(self, mark: Optional[Callable[[], tuple]] = None):
        super().__init__()
        self.events: List[dict] = []
        self._mark = mark or (lambda: ())

    def writable(self) -> bool:
        return True

    def write(self, s: str) -> int:
        now = time.monotonic()
        for line in s.splitlines():
            if line.strip():
                ev = json.loads(line)
                ev["mono"] = now
                ev["mark"] = self._mark()
                self.events.append(ev)
        return len(s)

    def spans(self, name: str) -> List[Tuple[dict, dict]]:
        """(begin, end) event pairs of every closed span called ``name``, in
        the order they began."""
        stack: List[dict] = []
        out = []
        for ev in self.events:
            if ev.get("name") != name:
                continue
            if ev.get("ph") == "B":
                stack.append(ev)
            elif ev.get("ph") == "E" and stack:
                out.append((stack.pop(), ev))
        return sorted(out, key=lambda be: be[0]["mono"])

    def durations(self, name: str, lo: float = float("-inf"),
                  hi: float = float("inf")) -> List[float]:
        """Durations (s) of the ``name`` spans that ended inside [lo, hi]."""
        return [e["dur_s"] for b, e in self.spans(name)
                if lo <= e["mono"] <= hi]
