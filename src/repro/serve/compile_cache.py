"""Warm megastep executables for the serving layer (DESIGN.md §4.2).

A streaming executor's first ``pump`` pays a trace+compile stall — tens of
milliseconds to seconds, charged to whichever request had the bad luck of
arriving first after a pool was created or resized.  Under continuous
batching that stall happens *inside* the dispatch lane, so every tenant on
the pool eats it.  This module moves the cost to ``register_graph`` time:

  * :func:`build_warm_megastep` AOT-lowers and compiles the exact megastep
    a :class:`~repro.fpp.streaming.StreamingExecutor` would trace for the
    same parameters — both sides build through
    ``streaming.build_stream_engine`` / ``build_stream_megastep``, so the
    compiled program and the would-have-been-traced one are the same
    function of the same baked graph constants (``session.prepared`` caches
    one (BlockGraph, perm) per session, so "same graph" is by identity,
    not just by value).
  * :class:`MegastepCache` memoizes those executables under
    ``(graph, kind, K, capacity, fused, alpha, eps, schedule, seed,
    session_uid)`` — the uid (:func:`session_uid`) pins the executable to
    the session whose constants it baked in, so a cache shared across
    servers can never hand one graph's program to a different graph that
    happens to reuse the same registered name.  Capacity is the raw lane
    count — the *server* snaps demand to pow2
    buckets (``planner.pow2_bucket``) before asking, which keeps the set
    of distinct compiled shapes logarithmic in load instead of linear.

Anything that changes the traced program must be in the key: ``alpha`` and
``eps`` are closed over by the push algebra, ``schedule`` picks the
on-device partition policy, ``fused`` swaps the visit body, ``seed`` feeds
the engine's scheduler PRNG stream.  Yield-config overrides are deliberately
*not* keyed — the serving layer never passes one (it always uses the
planner default for (kind, graph)); hand-rolled executors with custom yield
configs should not share this cache.

Compiles run outside the cache lock (a per-key in-flight event dedupes
concurrent warmers), so a background warm thread never blocks admission.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import visit as _visit
from repro.fpp.streaming import build_stream_engine, build_stream_megastep

_uid_lock = threading.Lock()
_uid_counter = itertools.count()


def session_uid(session) -> int:
    """A process-unique token for this session, minted on first use.

    The compiled megastep bakes in the session's graph constants
    (``session.prepared`` caches one BlockGraph per session), so cache
    keys must identify the *session*, not just its registered name —
    two servers sharing a :class:`MegastepCache` may both call a
    different graph ``"default"``.  A stored attribute rather than
    ``id(session)``: ids are recycled after GC, a minted uid never is.
    """
    uid = getattr(session, "_megastep_cache_uid", None)
    if uid is None:
        with _uid_lock:
            uid = getattr(session, "_megastep_cache_uid", None)
            if uid is None:
                uid = next(_uid_counter)
                session._megastep_cache_uid = uid
    return uid


def warm_key(session, graph: str, kind: str, k_visits: int, capacity: int, *,
             fused: bool = False, alpha: float = 0.15, eps: float = 1e-4,
             schedule: str = "priority", seed: int = 0, k: int = 8,
             length: int = 32, walk_seed: int = 0) -> tuple:
    """The cache key: every parameter that reaches the traced program,
    including the identity of the session whose graph constants the
    executable bakes in (:func:`session_uid`).  ``k`` (the kreach hop
    budget) shifts the engine's finalize, ``length``/``walk_seed``
    parameterize the rw walk visit — each reaches some kind's compiled
    program, so each is in every key."""
    return (str(graph), str(kind), int(k_visits), int(capacity),
            bool(fused), float(alpha), float(eps), str(schedule), int(seed),
            int(k), int(length), int(walk_seed), session_uid(session))


def build_warm_megastep(session, kind: str, capacity: int, *,
                        schedule: str = "priority", alpha: float = 0.15,
                        eps: float = 1e-4, seed: int = 0, k_visits: int = 64,
                        fused: bool = False, k: int = 8, length: int = 32,
                        walk_seed: int = 0):
    """AOT-compile the streaming megastep for these parameters.

    Returns a ``jax.stages.Compiled`` with the executor's calling
    convention ``(state, counter, limit, key) -> (state', MegastepStats)``
    — ``counter``/``limit`` are int32 scalars and ``key`` a PRNG key, so
    one executable serves every chunk the executor will ever dispatch at
    this capacity.  Injected via ``StreamingExecutor(megastep=...)`` (or
    ``session.stream(megastep=...)``) it replaces the trace the executor
    would otherwise do on first pump.

    ``kind="rw"`` has no megastep — its lane is the buffered walk visit —
    so the warm executable is the AOT-compiled ``make_walk_visit`` for
    (``length``, ``walk_seed``) at this capacity, injected via
    ``WalkExecutor(visit=...)`` through the same ``megastep=`` plumbing.
    """
    if kind == "rw":
        from repro.core.engine import DeviceGraph
        from repro.core.randomwalk import make_walk_visit
        from repro.core.yielding import NO_YIELD
        bg, _perm = session.prepared()
        dg = DeviceGraph.build(bg, NO_YIELD, int(capacity))
        visit = make_walk_visit(dg, int(length), int(walk_seed))
        Q = int(capacity)
        zi = jnp.zeros(Q, jnp.int32)
        occ = jnp.zeros((Q, dg.num_parts * dg.block_size), jnp.float32)
        return visit.lower(zi, zi, zi, zi, jnp.zeros(Q, jnp.uint32), occ,
                           jnp.int32(0)).compile()
    engine, _bg, _perm = build_stream_engine(
        session, kind, int(capacity), schedule=schedule, alpha=alpha,
        eps=eps, seed=seed, k_visits=k_visits, fused=fused, k=k)
    megastep = build_stream_megastep(engine, schedule)
    state = _visit.init_engine_state(
        engine.algebra, engine.dg, np.empty(0, dtype=np.int64),
        num_queries=int(capacity))
    return megastep.lower(state, jnp.int32(0),
                          jnp.int32(engine.k_visits),
                          jax.random.PRNGKey(seed)).compile()


class MegastepCache:
    """Thread-safe LRU memo of warm megastep executables.

    ``get_or_build`` is the one entry point: a hit returns instantly, a
    miss compiles *outside* the lock while other keys stay available, and
    two threads racing on the same key compile once (the loser waits on
    the winner's in-flight event).  ``warm_async`` wraps it in a daemon
    thread for register-time prewarming that must not block registration.

    ``max_entries`` bounds the memo: a long-lived multi-graph server (or a
    ``bench_serve`` sweep re-registering graphs across points) would
    otherwise accumulate one executable per distinct key forever.  The
    default is generous — pow2 capacity snapping already keeps the live
    key set logarithmic in load, so eviction only bites when graphs churn
    — and every hit/peek refreshes recency, so what gets dropped is the
    executable nothing has asked for longest (``evictions`` in
    ``stats()`` counts the drops).
    """

    def __init__(self, max_entries: int = 256):
        if max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self._lock = threading.Lock()
        self._cache: "collections.OrderedDict[tuple, object]" = \
            collections.OrderedDict()
        self._inflight: Dict[tuple, threading.Event] = {}
        self.max_entries = int(max_entries)
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.compile_s = 0.0      # total seconds spent compiling

    def __len__(self) -> int:
        with self._lock:
            return len(self._cache)

    def peek(self, key: tuple):
        """The executable if already warm, else None; never compiles.
        A found key is refreshed — a peeked executable is about to be
        injected into an executor, which is as live as a hit."""
        with self._lock:
            exe = self._cache.get(key)
            if exe is not None:
                self._cache.move_to_end(key)
            return exe

    def get_or_build(self, session, graph: str, kind: str, capacity: int, *,
                     k_visits: int = 64, fused: bool = False,
                     alpha: float = 0.15, eps: float = 1e-4,
                     schedule: str = "priority", seed: int = 0,
                     k: int = 8, length: int = 32, walk_seed: int = 0):
        key = warm_key(session, graph, kind, k_visits, capacity, fused=fused,
                       alpha=alpha, eps=eps, schedule=schedule, seed=seed,
                       k=k, length=length, walk_seed=walk_seed)
        while True:
            with self._lock:
                if key in self._cache:
                    self.hits += 1
                    self._cache.move_to_end(key)
                    return self._cache[key]
                ev = self._inflight.get(key)
                if ev is None:
                    self._inflight[key] = ev = threading.Event()
                    self.misses += 1
                    building = True
                else:
                    building = False
            if not building:
                ev.wait()
                continue        # winner published (or failed) — re-check
            try:
                t0 = time.perf_counter()
                exe = build_warm_megastep(
                    session, kind, capacity, schedule=schedule, alpha=alpha,
                    eps=eps, seed=seed, k_visits=k_visits, fused=fused,
                    k=k, length=length, walk_seed=walk_seed)
                with self._lock:
                    self._cache[key] = exe
                    self._cache.move_to_end(key)
                    while len(self._cache) > self.max_entries:
                        self._cache.popitem(last=False)
                        self.evictions += 1
                    self.compile_s += time.perf_counter() - t0
                return exe
            finally:
                with self._lock:
                    self._inflight.pop(key, None)
                ev.set()

    def warm_async(self, session, graph: str, kind: str, capacity: int,
                   on_error: Callable[[Exception], None],
                   **params) -> threading.Thread:
        """Fire-and-forget prewarm; returns the (daemon) thread for tests
        that want to join it.  A failed compile is never dropped: it is
        handed to ``on_error`` (the server records it and re-raises it to
        its callers)."""
        def warm():
            try:
                self.get_or_build(session, graph, kind, capacity, **params)
            except Exception as exc:  # noqa: BLE001 — handed on
                on_error(exc)

        t = threading.Thread(
            target=warm, name=f"warm-{graph}-{kind}-{capacity}",
            daemon=True)
        t.start()
        return t

    def stats(self) -> dict:
        with self._lock:
            return {"size": len(self._cache), "hits": self.hits,
                    "misses": self.misses, "evictions": self.evictions,
                    "max_entries": self.max_entries,
                    "compile_s": round(self.compile_s, 3)}
