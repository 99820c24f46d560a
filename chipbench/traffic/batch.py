"""Batch driver: forked queries in back-to-back batches through
``FPPSession.run``, the way an analytics job (landmark labels, BC source
batches, NCP seed fleets) drives the engine.

Each batch holds ``queries_per_batch`` sources drawn uniformly, without
repeats, over every vertex, fresh per batch from the seed.  The window
runs whole batches until one ends past ``--seconds``; the end-to-end
metric is the queries completed over the wall time of those batches.

Cell parameters (``params`` of the cell file):

* ``kind``: ``sssp`` or ``ppr``.
* ``queries_per_batch``: the batch size Q the session is planned for.
* ``keep_per_batch``: answers of each batch kept for the comparison,
  drawn from the seed before the window; ``check`` of them, drawn again,
  are compared once the window has closed.
* ``trace_batches``: how many batches a traced run records.

The configuration gives ``alpha`` and ``eps`` for ``ppr``.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np


class System:
    """A planned session whose batch program has run once."""

    def __init__(self, cell, data, seed: int):
        from repro.fpp import FPPSession
        cfg, params = cell.config, cell.params
        self.kind = params["kind"]
        self.q = int(params["queries_per_batch"])
        self.params, self.seed = params, seed
        self.run_kw = ({"alpha": float(cfg["alpha"]), "eps": float(cfg["eps"])}
                       if self.kind == "ppr" else {})
        self.n = data.edges.n
        self.session = FPPSession(data.program).plan(
            num_queries=self.q, block_size=cfg.get("block_size"))
        # one visit of the batch program: it compiles (or loads) the same
        # megastep every batch of the window runs, and stages the graph
        warm = np.random.default_rng([seed, 6]).choice(self.n, self.q,
                                                       replace=False)
        self.session.run(self.kind, warm, max_visits=1, **self.run_kw)

    def partitioning(self):
        from repro.core.queries import WEIGHT_VARIANTS
        return self.session.prepared(
            weights=WEIGHT_VARIANTS.get(self.kind, "natural"))


@contextlib.contextmanager
def visit_counts(sink: list):
    """Record each megastep chunk's per-partition visit counts (the
    program's own ``MegastepStats.visit_counts``) while the block runs."""
    from repro.core import visit

    inner_make = visit.make_megastep

    class Counting:
        def __init__(self, inner):
            self.inner = inner

        def __call__(self, *args):
            state, stats = self.inner(*args)
            sink.append(stats.visit_counts)
            return state, stats

        def __getattr__(self, name):
            return getattr(self.inner, name)

    visit.make_megastep = lambda *a, **k: Counting(inner_make(*a, **k))
    try:
        yield
    finally:
        visit.make_megastep = inner_make


def draws(seed: int, n: int, q: int, keep: int):
    """Per batch of the window: its sources, uniform without repeats over
    the ``n`` vertices, and the rows of it kept for the comparison."""
    rng = np.random.default_rng([seed, 7])
    keep_rng = np.random.default_rng([seed, 8])
    while True:
        yield rng.choice(n, q, replace=False), keep_rng.choice(q, keep,
                                                               replace=False)


def pick(seed: int, kept: int, check: int) -> np.ndarray:
    """Which of the kept answers are compared."""
    return np.sort(np.random.default_rng([seed, 9]).choice(
        kept, size=min(check, kept), replace=False))


def control_sources(cell, n: int, seed: int, seconds: float,
                    count: int) -> np.ndarray:
    """The sources that a window of three batches (the fewest a window
    runs) compares for ``seed``, for the control to answer in the
    program's place; at most ``count`` of them."""
    params = cell.params
    batches = draws(seed, n, int(params["queries_per_batch"]),
                    int(params["keep_per_batch"]))
    kept = np.concatenate([src[rows] for src, rows in
                           (next(batches) for _ in range(3))])
    return kept[pick(seed, kept.size, min(count, int(params["check"])))]


def prepare(cell, data, seed: int) -> System:
    return System(cell, data, seed)


def measure(system: System, seconds: float, tracer) -> dict:
    from chipbench.compare import Answer
    sess, q, params = system.session, system.q, system.params
    batches = draws(system.seed, system.n, q, int(params["keep_per_batch"]))
    trace_batches = int(params.get("trace_batches", 1)) if tracer.on else 0
    kept, counts = [], []
    visits = edges = syncs = 0.0
    batch_s = []
    t0 = time.perf_counter()
    while True:
        b = len(batch_s)
        sources, rows = next(batches)
        traced = b < trace_batches
        if traced:
            tracer.start()
        tb = time.perf_counter()
        with (visit_counts(counts) if traced else contextlib.nullcontext()), \
                tracer.span("chipbench.batch"):
            res = sess.run(system.kind, sources, **system.run_kw)
        batch_s.append(time.perf_counter() - tb)
        if traced and b + 1 == trace_batches:
            tracer.stop()
        visits += res.stats["visits"]
        syncs += res.stats["host_syncs"]
        edges += float(res.edges_processed.sum())
        kept.extend(Answer(system.kind, int(sources[r]), res.values[r].copy(),
                           residual=(None if res.residual is None
                                     else res.residual[r].copy()))
                    for r in rows)
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    if tracer.on and len(batch_s) < trace_batches:
        tracer.stop()
    nb = len(batch_s)
    partitioning = system.partitioning() if tracer.on else None
    return {
        "attempted": nb * q,
        "failed": 0,
        "end_to_end": {"batch_queries_per_s": nb * q / window_s},
        "notes": [f"batch: {nb} batches of {q} {system.kind} queries in "
                  f"{window_s:.3f} s; per batch "
                  + ", ".join(f"{s:.3f}" for s in batch_s) + " s"],
        "counters": {
            "kind": system.kind, "queries": nb * q,
            "window_s": window_s - tracer.overhead_s,
            "visits": visits, "edges": edges, "host_syncs": syncs,
            "num_queries": q,
            "traced_visit_counts": (np.sum([np.asarray(c, dtype=np.int64)
                                            for c in counts], axis=0)
                                    if counts else None),
            "block_graph": partitioning[0] if partitioning else None,
        },
        "answers": [kept[i] for i in pick(system.seed, len(kept),
                                          int(params["check"]))],
    }


def release(system: System):
    system.session = None
