"""The program's own host spans in a traced run's profile.

The program records ``fpp.*`` spans on its batch path (a
``TraceAnnotation`` each, with its counts as event stats) in the host
plane of the same ``.xplane.pb`` as the device ops.  This module reads
them, with their stats, and the benchmark's ``chipbench.window`` span, so
that a per-layer metric is a share of the window or a mean per chunk.

The names are spelled here, not imported from the program: a span the
program renames shows as a metric that reads nothing, not as one that
quietly follows the new name.  A program that records no such span (an
older commit) gives every reader ``None``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

from chipbench import trace

RUN = "fpp.run"
ENGINE = "fpp.engine"
INIT_STATE = "fpp.init_state"
DISPATCH = "fpp.dispatch"
SYNC = "fpp.sync"
HARVEST = "fpp.harvest"
FINALIZE = "fpp.finalize"
PREFIX = "fpp."


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float
    args: Dict[str, object]


@dataclasses.dataclass
class Spans:
    """The program's spans that overlap the window, and the window (ns)."""
    window: Tuple[float, float]
    spans: List[Span]

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def clipped_ns(self, span: Span) -> float:
        lo, hi = self.window
        return max(0.0, min(span.end_ns, hi) - max(span.start_ns, lo))

    def share_pct(self, spans: List[Span]) -> Optional[float]:
        """The union of ``spans`` inside the window, in % of the window;
        None when there is no span to read."""
        if not spans or self.window_ns <= 0:
            return None
        return 100.0 * trace.union_ns(spans, *self.window) / self.window_ns


@functools.lru_cache(maxsize=2)
def load(path: str) -> Optional[Spans]:
    """The ``fpp.*`` spans of the profile at ``path`` that overlap its
    ``chipbench.window`` span; None without a window."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    window, found = None, []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == trace.WINDOW_SPAN:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name.startswith(PREFIX):
                    found.append(Span(e.name, e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      dict(e.stats)))
    if window is None:
        return None
    lo, hi = window
    return Spans(window, [s for s in found
                          if s.end_ns > lo and s.start_ns < hi])


def recorded() -> Optional[Spans]:
    """The spans of the run's own trace, None where it left none."""
    from chipbench import harness
    path = trace.latest_xplane(harness.TRACE_DIR)
    return None if path is None else load(path)
