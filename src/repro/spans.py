"""Named host spans on the run and serving paths (DESIGN.md §3.5).

A span is a ``jax.profiler.TraceAnnotation``: it lands in the profiler's
host plane of the same ``.xplane.pb`` as the device ops, on the same
clock, and nests under the span open on its thread.  Keyword arguments
become the event's stats; counts known only when the span ends are
attached with :func:`note`.  With no profiler recording a span costs
about a microsecond and :func:`note` nothing.  The profiler is the only
exporter: nothing is buffered here.

Capture with ``jax.profiler.trace(dir)`` around ``FPPSession.run`` or a
``GraphServer``'s lifetime.
"""
from __future__ import annotations

import jax

#: ``FPPSession.run``, the whole call (root of the batch path)
RUN = "fpp.run"
#: ``FPPSession.prepared``: partition cache lookup or build
PREPARE = "fpp.prepare"
#: the run's Δ-window, and ``FPPEngine.__init__``: device graph, visit
#: and megastep programs
ENGINE = "fpp.engine"
#: ``FPPEngine.init_state``: host planes, upload, scheduler metadata
INIT_STATE = "fpp.init_state"
#: one megastep call (its trace and lowering when it has none yet)
DISPATCH = "fpp.dispatch"
#: the blocking read of a chunk's visit count
SYNC = "fpp.sync"
#: the chunk's other device-to-host reads
HARVEST = "fpp.harvest"
#: device state to host result planes in original vertex ids
FINALIZE = "fpp.finalize"
#: ``FPPSession.ncp``'s sweep cuts over the batch's PPR vectors
SWEEP = "fpp.sweep"
#: ``GraphServer.submit``, ``_admit`` (per request) and delivery (per
#: response); each carries the request's ``rid``
SUBMIT = "serve.submit"
ADMIT = "serve.admit"
DELIVER = "serve.deliver"


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A context manager recording ``name`` with ``args`` as event stats."""
    return jax.profiler.TraceAnnotation(name, **args)


def note(sp: jax.profiler.TraceAnnotation, **args) -> None:
    """Attach ``args`` to the open span ``sp`` when a profiler records."""
    if sp.is_enabled():
        sp.set_metadata(**args)
