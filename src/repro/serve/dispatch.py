"""Concurrent serving lanes: dispatch workers + the delivery lane.

The continuous-batching :class:`~repro.serve.graph_server.GraphServer`
(DESIGN.md §4.2) splits serving into three lanes:

  * **admission** — caller threads in ``GraphServer.submit`` (backlog,
    dedup, fair-queueing bookkeeping; never touches an executor);
  * **pumping** — one :class:`PoolWorker` thread per lane pool, driving
    ``StreamingExecutor.pump`` chunk after chunk and refilling lanes at
    every chunk boundary;
  * **delivery** — one :class:`DeliveryWorker` turning finished lanes
    into ``GraphResponse``\\ s and waking blocked ``result()`` callers.

This module owns the two background lanes; the server owns all shared
state and its one lock.  Why the threads compose safely: every structure
has exactly one lock.  Server-side state (backlogs, tickets, virtual
times, responses) is guarded by the server lock; executor state by the
executor's own lock, acquired strictly after the server lock and never
the other way around.  A worker admits under the server lock, then pumps
*outside* it (the executor lock serializes the chunk), so a chunk in
flight never blocks submissions — a submit racing its own pool's chunk
simply parks on the executor lock and lands at the next chunk boundary,
the only point where lane mutation was ever legal (§3.3 exactness).

A lane never dies silently: an exception in a worker (a compile refused
by the device, an HBM allocation failure) is recorded on the server, the
lanes halt, and ``result()``/``serve()``/``shutdown()`` re-raise it in
the caller's thread instead of letting it wait out a timeout.

Pool workers replace the synchronous path's explicit pool arbitration:
each pool pumps on its own thread and the OS scheduler interleaves them,
while request priorities still shape *admission order* within a pool.
The synchronous ``GraphServer.step``/``serve`` path (the parity oracle)
keeps the original ``PartitionScheduler`` arbitration.
"""
from __future__ import annotations

import queue
import threading


class PoolWorker(threading.Thread):
    """The pump lane for one (graph, kind) pool.

    Per iteration, under the server lock: police deadlines, take a resize
    hint (idle pools only), admit queued requests into free lanes.  Then
    *outside* the lock: either warm the resize target through the compile
    cache and apply it, or pump one megastep chunk and hand finished
    lanes to the delivery queue.  Idle pools park on their condition
    variable (woken by ``submit``) with a short timeout so deadline
    policing and shutdown flags are still observed while quiet.
    """

    def __init__(self, server, pool):
        super().__init__(name=f"pump-{pool.graph}-{pool.kind}", daemon=True)
        self.server = server
        self.pool = pool

    def run(self):
        try:
            self._pump()
        except Exception as exc:  # noqa: BLE001 — handed to callers
            self.server._fail(exc)

    def _pump(self):
        srv, pool = self.server, self.pool
        while True:
            with srv._lock:
                if not srv._running or pool.retired:
                    # retired: update_graph replaced this pool's graph —
                    # the pool was drained by contract, so exiting loses
                    # nothing; fresh pools get fresh workers
                    return
                now = srv.clock()
                srv._police_pool(pool, now)
                hint = srv._resize_hint(pool)
                if hint is None:
                    srv._admit(pool, now)
                    if not pool.active:
                        pool.cv.wait(timeout=srv.idle_wait_s)
                        continue
                    if not srv._take_round():
                        return
            if hint is not None:
                # compile outside the lock: a cache miss (seconds) must
                # not stall admission to other pools
                exe = srv._warm_executable(pool, hint)
                with srv._lock:
                    if srv._running and pool.active == 0 \
                            and pool.capacity != hint:
                        srv._apply_resize(pool, hint, exe)
                continue
            pool.exec.pump(srv.k_visits)
            done = pool.exec.take_finished()
            if done:
                srv._queue_delivery(pool, done)


class DeliveryWorker(threading.Thread):
    """The delivery lane: a queue of (pool, finished qids) batches from
    the pump workers, turned into responses under the server lock.

    Decoupling delivery from pumping means a pool's next chunk dispatches
    while the previous chunk's answers are still being built/fanned out.
    ``stop()`` enqueues a sentinel; the server joins pump workers first,
    so every delivery batch precedes the sentinel and none is dropped.
    """

    def __init__(self, server):
        super().__init__(name="serve-delivery", daemon=True)
        self.server = server
        self.q: queue.Queue = queue.Queue()

    def put(self, pool, qids):
        self.q.put(("lanes", pool, list(qids)))

    def put_cached(self, rid, entry):
        """Queue one result-cache hit: same delivery lane, same
        ``result()``/``poll()`` wake-up path as a lane-computed answer —
        a cached response is distinguishable only by its stats."""
        self.q.put(("cached", rid, entry))

    def stop(self):
        self.q.put(None)

    def run(self):
        try:
            self._drain()
        except Exception as exc:  # noqa: BLE001 — handed to callers
            self.server._fail(exc)

    def _drain(self):
        srv = self.server
        while True:
            item = self.q.get()
            if item is None:
                return
            tag, a, b = item
            with srv._lock:
                if tag == "cached":
                    srv._finish_cached(a, b, srv.clock())
                else:
                    srv._deliver(a, b, srv.clock())
