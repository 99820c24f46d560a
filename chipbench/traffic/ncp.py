"""NCP driver: fleets of PPR seeds through ``FPPSession.ncp``, the way a
network-community-profile job drives the engine: each batch's PPR vectors
are swept for their best-conductance clusters, and the job keeps the
minimum conductance per cluster-size bin.

Each batch holds ``queries_per_batch`` seeds drawn uniformly, without
repeats, over every vertex, fresh per batch from the seed (``batch.py``'s
``draws``; a graph with fewer vertices than that, as in a shrunken
rehearsal, gives batches of all of them).  The window runs whole batches
until one ends past ``--seconds``; the end-to-end metric is the seeds
profiled over the wall time of those batches, sweeps included.  The
window's profile is the binwise minimum over its batches.

Cell parameters are ``batch.py``'s (``kind`` is ``ppr``); the
configuration gives ``alpha``, ``eps`` and ``delivery``: ``support``, the
job's own, has each batch's PPR vectors come back as their supports
(``FPPSession.run(..., delivery="support")``), and a program without that
delivery cannot run the configuration, which set-up says at once.
Besides the sampled PPR rows that ``compare.py`` reads, the driver keeps
one whole batch, chosen from the seed, and after the window recomputes
its profile with the plain whole-graph sweep below, which reads only the
benchmark's own edge list.  A profile that differs in any bin counts the
batch's seeds as failed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import inspect
import time

import numpy as np

from chipbench.traffic import batch
from chipbench.traffic.batch import draws, pick, visit_counts


class System(batch.System):
    """A planned session whose PPR program has run once, and the edge list
    the plain sweep reads."""

    def __init__(self, cell, data, seed: int):
        super().__init__(cell, data, seed)
        self.edges = data.edges
        if _delivery(cell) != "dense":
            self.run_kw = dict(self.run_kw, delivery=_delivery(cell))
        # one batch's delivery and sweep, as the window runs them
        warm = np.random.default_rng([seed, 6]).choice(self.n, self.q,
                                                       replace=False)
        self.session.ncp(warm, max_visits=1, **self.run_kw)


def _delivery(cell) -> str:
    """The configuration's delivery, which the program has to offer."""
    from repro.fpp import FPPSession
    delivery = cell.config.get("delivery", "dense")
    if (delivery != "dense" and "delivery"
            not in inspect.signature(FPPSession.run).parameters):
        raise SystemExit(
            f"{cell.name}: this program's FPPSession.run has no "
            f"delivery={delivery!r}, which {cell.config_name} states")
    return delivery


def _fitted(cell, n: int):
    """``cell`` with its batch no larger than the ``n`` vertices it draws
    distinct seeds from."""
    q = min(int(cell.params["queries_per_batch"]), n)
    return dataclasses.replace(cell, params=dict(cell.params,
                                                 queries_per_batch=q))


def reference_profile(edges, pvals: np.ndarray) -> np.ndarray:
    """The NCP of the rows of ``pvals`` [Q, n] by the plain sweep: each row's
    support ``{v : p(v) > 0}`` ordered by ``-p/deg`` (stable: ties in
    ascending vertex id), every arc of the graph scanned for the internal
    ones, conductance ``cut / min(vol, 2m - vol)`` of each prefix, then the
    minimum per bin ``floor(log2(size))`` of ``ceil(log2(n)) + 1``."""
    deg = edges.out_degree().astype(np.float64)
    m2 = float(deg.sum())
    nbins = int(np.ceil(np.log2(edges.n))) + 1
    best = np.full(nbins, np.inf)
    for p in pvals:
        support = np.flatnonzero(p > 0)
        if support.size < 2:
            continue
        score = p[support] / np.maximum(deg[support], 1.0)
        order = support[np.argsort(-score, kind="stable")]
        rank = np.full(edges.n, order.size, dtype=np.int64)
        rank[order] = np.arange(order.size)
        rs, rd = rank[edges.src], rank[edges.dst]
        both = (rs < order.size) & (rd < order.size)
        internal = np.bincount(np.maximum(rs[both], rd[both]),
                               minlength=order.size).astype(np.float64)
        vol = np.cumsum(deg[order])
        cut = vol - np.cumsum(internal)
        denom = np.minimum(vol, m2 - vol)
        cond = np.full(order.size, np.inf)
        keep = denom > 0
        cond[keep] = cut[keep] / denom[keep]
        bins = np.minimum(np.log2(np.arange(1, order.size + 1))
                          .astype(np.int64), nbins - 1)
        np.minimum.at(best, bins, cond)
    return best


def _row(plane, r: int) -> np.ndarray:
    """Row ``r`` of a delivered plane, dense and the caller's own."""
    return plane.row(r) if hasattr(plane, "row") else plane[r].copy()


def _dense(plane) -> np.ndarray:
    return plane.dense() if hasattr(plane, "dense") else plane


def control_sources(cell, n: int, seed: int, seconds: float,
                    count: int) -> np.ndarray:
    """``batch.control_sources`` for the batches this driver runs."""
    return batch.control_sources(_fitted(cell, n), n, seed, seconds, count)


def prepare(cell, data, seed: int) -> System:
    _delivery(cell)     # before set-up: a program without it stops here
    return System(_fitted(cell, data.edges.n), data, seed)


def measure(system: System, seconds: float, tracer) -> dict:
    from chipbench.compare import Answer
    sess, q, params = system.session, system.q, system.params
    batches = draws(system.seed, system.n, q, int(params["keep_per_batch"]))
    whole_at = int(np.random.default_rng([system.seed, 10]).integers(3))
    trace_batches = int(params.get("trace_batches", 1)) if tracer.on else 0
    kept, counts = [], []
    visits = edges = syncs = 0.0
    batch_s = []
    profile = whole = None
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
            prof, res = sess.ncp(sources, **system.run_kw)
        batch_s.append(time.perf_counter() - tb)
        if traced and b + 1 == trace_batches:
            tracer.stop()
        profile = prof if profile is None else np.minimum(profile, prof)
        if whole is None or b <= whole_at:
            # the chosen batch, or the last one if the window ends first
            whole = (b, prof, res)
        visits += res.stats["visits"]
        syncs += res.stats["host_syncs"]
        edges += float(res.edges_processed.sum())
        kept.extend(Answer("ppr", int(sources[r]), _row(res.values, r),
                           residual=_row(res.residual, r)) for r in rows)
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    if tracer.on and len(batch_s) < trace_batches:
        tracer.stop()
    nb = len(batch_s)
    partitioning = system.partitioning() if tracer.on else None
    b, prof, res = whole
    want = reference_profile(system.edges, _dense(res.values))
    differ = (np.arange(want.size) if prof.shape != want.shape
              else np.flatnonzero(prof != want))
    finite = np.isfinite(profile)
    return {
        "attempted": nb * q,
        "failed": q if differ.size else 0,
        "end_to_end": {"batch_queries_per_s": nb * q / window_s},
        "notes": [f"ncp: {nb} batches of {q} ppr seeds in {window_s:.3f} s; "
                  "per batch " + ", ".join(f"{s:.3f}" for s in batch_s)
                  + " s",
                  f"ncp: window profile, min conductance by log2 size bin: "
                  + ", ".join(f"{i}:{c:.6g}" for i, c in
                              zip(np.flatnonzero(finite), profile[finite])),
                  (f"ncp: batch {b}'s profile differs from the plain sweep "
                   f"in bins {differ.tolist()}" if differ.size else
                   f"ncp: batch {b}'s profile equals the plain sweep in all "
                   f"{want.size} bins")],
        "counters": {
            "kind": "ppr", "queries": nb * q,
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
