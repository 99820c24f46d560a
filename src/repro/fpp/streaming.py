"""Streaming FPP execution: queries that arrive over time (DESIGN.md §3.3).

``examples/serve_batched.py``'s ContinuousBatcher keeps an LM decode batch
full by refilling finished slots between decode steps.  This module is the
same idea for graph queries: the engine state carries ``capacity`` query
lanes, and between partition visits the executor

  * **admits** queued queries into free lanes by injecting their source op
    into the partition buffer (exactly how a one-shot run initializes, so
    late arrivals are indistinguishable from early ones),
  * **harvests** lanes whose queries have no pending buffered op anywhere
    (queries are independent, so per-lane completion is exact), records
    their values, and recycles the lane.

The visits between those boundaries run as device-resident K-visit
*megasteps* (``core/visit.make_megastep``): partition selection happens on
device and the host is consulted once per chunk, not once per visit.
Admission and harvest move to chunk boundaries — which the DESIGN.md §3.3
exactness argument already permits: admission only adds ops a one-shot run
would have started with, and harvesting later never changes a finished
lane's values, so chunking delays *when* lanes recycle, never *what* a
query answers.

Everything mode-specific — what a buffered op means, when a lane is pending,
what a partition's priority is — comes from the engine's ``core/visit.py``
algebra, so minplus (sssp/bfs) and push (ppr) lanes stream through the same
loop.  Because yielding/scheduling never change results (paper §5.1) and
admission only adds ops a one-shot run would have started with, a staggered
streaming run returns bit-identical minplus answers to the one-shot run of
the union, and push answers within the same eps tolerance the one-shot run
carries — ``tests/test_fpp_session.py`` pins both properties.

Concurrency contract (DESIGN.md §4.2): every public entry point —
``submit``, ``step``, ``pump``, ``run``, ``take_finished`` — serializes on
one executor lock, and ``pump`` holds it for whole chunks, so a submitter
on another thread joins exactly at a megastep chunk boundary: the only
point where touching lanes was ever legal.  Thread safety here is the same
rule as exactness, enforced by a lock instead of an argument.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core import visit as _visit
from repro.core.engine import FPPEngine
from repro.core.scheduler import PartitionScheduler
from repro.core.yielding import YieldConfig
from repro.fpp import planner as _planner


def build_stream_engine(session, kind: str, capacity: int, *,
                        schedule: str = "priority",
                        yield_config: Optional[YieldConfig] = None,
                        alpha: float = 0.15, eps: float = 1e-4,
                        seed: int = 0, k_visits: int = 64,
                        fused: bool = False,
                        k: int = 8) -> Tuple[FPPEngine, object,
                                             np.ndarray]:
    """(engine, bg, perm) exactly as a :class:`StreamingExecutor` for the
    same arguments would build them.

    The one construction path shared by the executor and the serving
    compile cache (``serve/compile_cache.py``): a megastep AOT-compiled
    from this engine is interchangeable with the one the executor would
    trace itself, because the graph staging (``session.prepared`` is
    cached per session), yield config, algebra parameters, and chunk size
    all come from here.  ``k`` is the kreach hop budget (ignored by other
    kinds); the stride comes from the session so the shift variant and the
    decode can never disagree.
    """
    from repro.core.queries import WEIGHT_VARIANTS
    from repro.fpp.backends import _ENGINE_MODE
    bg, perm = session.prepared(weights=WEIGHT_VARIANTS.get(kind, "natural"))
    yc = (yield_config if yield_config is not None
          else _planner.default_yield_config(kind, bg))
    engine = FPPEngine(bg, mode=_ENGINE_MODE[kind],
                       num_queries=int(capacity),
                       yield_config=yc, schedule=schedule, alpha=alpha,
                       eps=eps, seed=seed, k_visits=int(k_visits),
                       fused=bool(fused), hop_budget=int(k),
                       hop_stride=(session.kreach_stride
                                   if kind == "kreach" else 1.0))
    return engine, bg, perm


def build_stream_megastep(engine: FPPEngine, schedule: str) -> Callable:
    """The streaming pump's megastep for ``engine``: the §2.3 K-visit chunk
    with the [Q] pending-lane harvest mask folded into the same dispatch
    (``harvest_mask=True``) — what ``pump`` runs and what the serving
    compile cache warms ahead of time."""
    return _visit.make_megastep(
        engine.dg, engine.algebra, engine.max_rounds, policy=schedule,
        K=engine.k_visits, harvest_mask=True, fused=engine.fused,
        frontier_mode=engine.frontier_mode)


@dataclasses.dataclass
class StreamQuery:
    """One admitted-or-queued query and, eventually, its answer.

    The ``*_visit`` fields are snapshots of the executor-global visit
    counter (queue wait = admitted - submitted, in-flight latency =
    finished - admitted, both in visits the whole executor ran); the
    ``*_sync`` fields snapshot ``host_syncs`` the same way, so a serving
    layer can bill exact per-request host round trips (DESIGN.md §4.2)."""
    qid: int
    source: int                 # original vertex id
    slot: int = -1
    submitted_visit: int = -1
    admitted_visit: int = -1
    finished_visit: int = -1
    admitted_sync: int = -1
    finished_sync: int = -1
    values: Optional[np.ndarray] = None      # [n] original ids, on completion
    residual: Optional[np.ndarray] = None    # push kinds
    edges: float = 0.0
    done: bool = False


class StreamingExecutor:
    """Admission queue + slot-recycling loop over the buffered engine.

    Mirrors serve/engine.py's ContinuousBatcher (DESIGN.md §4.1): ``submit``
    enqueues work, ``step`` runs one partition visit (admitting and
    harvesting around it), ``run`` drains everything submitted so far.
    ``pump(n)`` advances a bounded number of visits so callers can
    interleave arrivals.  ``serve/graph_server.py`` (DESIGN.md §4.2) stacks
    multi-tenant admission on top of this loop.

    ``k_visits`` is the device-resident chunk size: ``pump``/``run``
    dispatch megasteps of up to that many visits, and admission/harvest
    only happen at those chunk boundaries, so K is simultaneously the
    host-sync amortization factor and the lane-recycling latency.  The
    executor builds its megastep with ``harvest_mask=True`` so the [Q]
    pending-lane mask rides back in the same host sync as the chunk stats —
    harvesting costs no extra dispatch (core/visit.make_megastep).
    """

    def __init__(self, session, kind: str = "sssp", capacity: int = 16, *,
                 schedule: str = "priority",
                 yield_config: Optional[YieldConfig] = None,
                 alpha: float = 0.15, eps: float = 1e-4,
                 harvest_every: int = 1, seed: int = 0,
                 k_visits: int = 64, fused: bool = False,
                 megastep: Optional[Callable] = None, k: int = 8):
        if kind not in ("sssp", "bfs", "ppr", "cc", "kreach"):
            raise ValueError(f"streaming supports sssp/bfs/ppr/cc/kreach "
                             f"(rw streams via WalkExecutor), got {kind!r}")
        self.session = session
        self.kind = kind
        self.capacity = int(capacity)
        self.alpha, self.eps = alpha, eps
        self.k = int(k)
        # per-visit cadence of the legacy step() path; pump()/run() harvest
        # at megastep chunk boundaries instead
        self.harvest_every = max(1, int(harvest_every))
        self.engine, bg, perm = build_stream_engine(
            session, kind, self.capacity, schedule=schedule,
            yield_config=yield_config, alpha=alpha, eps=eps, seed=seed,
            k_visits=k_visits, fused=fused, k=k)
        self.bg, self.perm = bg, perm
        self.mode = self.engine.mode
        # own megastep with the pending-lane harvest mask folded into the
        # chunk dispatch (the engine's plain-run megastep skips it).  A
        # caller may inject a warm one (``megastep=``) — the serving
        # compile cache hands over programs AOT-compiled from an engine
        # built by the same :func:`build_stream_engine` call, so the
        # injected executable is the one this executor would have traced.
        self._megastep = (megastep if megastep is not None
                          else build_stream_megastep(self.engine, schedule))
        self.algebra = self.engine.algebra
        # serializes submit/step/pump/run/take_finished: a foreign-thread
        # submit lands exactly at a chunk boundary (module docstring)
        self._lock = threading.RLock()
        self.finished: collections.deque = collections.deque()
        self.scheduler = PartitionScheduler(schedule, bg.num_parts, seed)
        self.state = self._empty_state()
        self.queue: collections.deque = collections.deque()
        self.queries: Dict[int, StreamQuery] = {}
        self.free_slots: List[int] = list(range(self.capacity))
        self.slot_qid = np.full(self.capacity, -1, dtype=np.int64)
        self.visits = 0
        self.modeled_bytes = 0.0
        self.host_syncs = 0
        # query lanes that processed an edge, summed over the visits
        self.lane_visits = 0
        self._key = jax.random.PRNGKey(seed)
        self._lane_pending: Optional[np.ndarray] = None  # set by _chunk
        self._drained = False                            # set by _chunk
        self._next_qid = 0
        # per-lane edge counts: exact int32 per visit, float64 on host
        self._edges = np.zeros(self.capacity, dtype=np.float64)
        alg, deg = self.algebra, self.engine.dg.deg
        self._pending_q = jax.jit(lambda planes, buf: jnp.any(
            alg.pending(buf[:-1], planes, deg), axis=(0, 2)))
        self._prio_row = jax.jit(alg.prio_of)
        if self.mode == "cc":
            # cc admission buffers the whole label plane (every partition),
            # so the priority refresh runs vmapped over all rows at once
            self._cc_plane = jnp.asarray(_visit.cc_label_plane(bg))
            self._prio_all = jax.jit(jax.vmap(alg.prio_of))

    # ----------------------------------------------------------- lifecycle

    def _empty_state(self) -> _visit.VisitState:
        return _visit.init_engine_state(
            self.algebra, self.engine.dg,
            np.empty(0, dtype=np.int64), num_queries=self.capacity)

    def submit(self, sources: np.ndarray) -> List[int]:
        """Enqueue a batch of sources (original ids); returns their qids.

        Thread-safe: a submit racing a ``pump`` on another thread blocks
        until the in-flight chunk's boundary and is admitted there —
        indistinguishable from having arrived between chunks."""
        with self._lock:
            qids = []
            for s in np.atleast_1d(np.asarray(sources)):
                q = StreamQuery(qid=self._next_qid, source=int(s),
                                submitted_visit=self.visits)
                self._next_qid += 1
                self.queries[q.qid] = q
                self.queue.append(q.qid)
                qids.append(q.qid)
            self._admit()
            return qids

    # ----------------------------------------------------------- admission

    def _inject_plane(self, q: StreamQuery, slot: int):
        """cc admission: a cc lane's init is the whole label plane, not one
        source op — buffer it across every partition exactly as the
        one-shot run's ``init_ops`` does (the source only names the lane),
        then refresh every partition's priority row in one vmapped
        dispatch.  Late cc arrivals therefore converge to the identical
        labels a one-shot lane computes: same initial buffer, same
        fixpoint."""
        st = self.state
        P = self.bg.num_parts
        buf = st.buf.at[:P, slot, :].set(self.algebra.combine(
            st.buf[:P, slot, :], self._cc_plane))
        newprio, newops = self._prio_all(buf[:P], st.planes,
                                         self.engine.dg.deg)
        came_alive = (~np.isfinite(np.asarray(st.prio))
                      & np.isfinite(np.asarray(newprio)))
        stamp = jnp.where(jnp.asarray(came_alive), jnp.int32(self.visits),
                          st.stamp)
        self.state = st._replace(buf=buf, prio=jnp.asarray(newprio),
                                 ops_count=jnp.asarray(newops), stamp=stamp)
        q.slot = slot
        q.admitted_visit = self.visits
        q.admitted_sync = self.host_syncs
        self.slot_qid[slot] = q.qid

    def _inject(self, q: StreamQuery, slot: int):
        """Buffer the query's source op — identical to one-shot init, so the
        scheduler sees a late arrival as just another pending partition."""
        if self.mode == "cc":
            self._inject_plane(q, slot)
            return
        B = self.engine.dg.block_size
        src = int(self.perm[q.source])
        pv, lv = divmod(src, B)
        st = self.state
        was_empty = not np.isfinite(float(np.asarray(st.prio[pv])))
        buf = st.buf.at[pv, slot, lv].set(self.algebra.combine(
            st.buf[pv, slot, lv], jnp.float32(self.algebra.source_value)))
        planes_row = tuple(x[pv] for x in st.planes)
        newprio, newops = self._prio_row(buf[pv], planes_row,
                                         self.engine.dg.deg[pv])
        prio = st.prio.at[pv].set(newprio)
        ops = st.ops_count.at[pv].set(newops)
        stamp = st.stamp
        if was_empty and np.isfinite(float(np.asarray(newprio))):
            stamp = stamp.at[pv].set(jnp.int32(self.visits))
        self.state = st._replace(buf=buf, prio=prio, ops_count=ops,
                                 stamp=stamp)
        q.slot = slot
        q.admitted_visit = self.visits
        q.admitted_sync = self.host_syncs
        self.slot_qid[slot] = q.qid

    def _admit(self):
        while self.free_slots and self.queue:
            qid = self.queue.popleft()
            self._inject(self.queries[qid], self.free_slots.pop(0))

    # ------------------------------------------------------------- harvest

    def _reset_slot(self, slot: int):
        st = self.state
        planes = tuple(x.at[:, slot, :].set(v)
                       for x, v in zip(st.planes, self.algebra.plane_init))
        buf = st.buf.at[:, slot, :].set(self.algebra.identity)
        self.state = st._replace(planes=planes, buf=buf)
        self._edges[slot] = 0.0

    def _harvest(self, pending: Optional[np.ndarray] = None):
        """Finish every active lane with no pending op anywhere.

        ``pending`` is the [capacity] bool lane mask when the caller already
        has one (the megastep harvests it in the same dispatch as the chunk
        stats); without it a dedicated ``_pending_q`` dispatch runs — the
        legacy ``step()`` cadence."""
        active = self.slot_qid >= 0
        if not active.any():
            return
        st = self.state
        if pending is None:
            self.host_syncs += 1
            pending = np.asarray(self._pending_q(st.planes, st.buf))
        n = self.bg.n
        for slot in np.flatnonzero(active & ~pending):
            q = self.queries[int(self.slot_qid[slot])]
            vals = np.asarray(st.planes[0][:, slot, :]).reshape(-1)[:n]
            if self.mode == "push":
                rfull = (np.asarray(st.planes[1][:, slot, :])
                         + np.asarray(st.buf[:-1, slot, :])).reshape(-1)[:n]
                q.residual = rfull[self.perm].astype(np.float32)
            if self.mode == "kreach":
                # unpack the lexicographic (hops, dist) fixpoint with the
                # engine's stride/budget — elementwise, so decode-then-perm
                # equals perm-then-decode
                from repro.core.oracles import decode_kreach
                dv, dh = decode_kreach(vals[None, :], self.engine.hop_stride,
                                       self.engine.hop_budget)
                q.values = dv[0][self.perm].astype(np.float32)
                q.residual = dh[0][self.perm].astype(np.float32)
            elif self.mode == "cc":
                # raw reordered-rep labels -> canonical min-original-id
                # labels, after the perm mapping (same order as session.run)
                from repro.fpp.backends import canonicalize_cc
                q.values = canonicalize_cc(
                    vals[self.perm][None, :])[0]
            else:
                q.values = vals[self.perm].astype(np.float32)
            q.edges = float(self._edges[slot])
            q.finished_visit = self.visits
            q.finished_sync = self.host_syncs
            q.done = True
            self.finished.append(q.qid)
            self.slot_qid[slot] = -1
            self._reset_slot(int(slot))
            self.free_slots.append(int(slot))

    # ---------------------------------------------------------------- loop

    @property
    def active(self) -> int:
        return int((self.slot_qid >= 0).sum())

    @property
    def queue_depth(self) -> int:
        """Submitted-but-not-yet-admitted queries (free-lane starvation
        signal; GraphServer's autoscaling hint reads it)."""
        return len(self.queue)

    def take_finished(self) -> List[int]:
        """Drain the finished-lane queue: qids harvested since the last
        call, in completion order.  The serving delivery lane consumes
        this instead of scanning every query for ``done`` — and because
        ``_harvest`` appends under the executor lock while delivery pops
        here, a response is never observed half-built."""
        with self._lock:
            out = list(self.finished)
            self.finished.clear()
            return out

    def step(self) -> bool:
        """One partition visit (admit before, harvest after).  False when
        nothing is pending anywhere — all admitted queries are complete."""
        with self._lock:
            return self._step_locked()

    def _step_locked(self) -> bool:
        self._admit()
        st = self.state
        p = self.scheduler.select(np.asarray(st.prio), np.asarray(st.stamp),
                                  np.asarray(st.ops_count))
        if p is None:
            self._harvest()
            self._admit()
            return bool(self.queue) or self.active > 0
        self.state, (_, eq) = self.engine._visit(self.state, jnp.int32(p),
                                                 jnp.int32(self.visits))
        eq = np.asarray(eq)
        self._edges += eq
        self.lane_visits += int(np.count_nonzero(eq))
        self.visits += 1
        self.modeled_bytes += float(self.engine._visit_bytes[p])
        if self.visits % self.harvest_every == 0:
            self._harvest()
        return True

    def _chunk(self, limit: int) -> int:
        """One megastep dispatch of up to ``min(limit, K)`` visits; chunk
        stats AND the pending-lane harvest mask come back in that single
        host sync.  Returns visits executed."""
        limit = min(int(limit), self.engine.k_visits)
        if limit <= 0:
            self._lane_pending = None   # a stale mask must never be harvested
            return 0
        chunk = self.host_syncs
        with spans.span(spans.DISPATCH, chunk=chunk):
            st, ms = self._megastep(self.state, jnp.int32(self.visits),
                                    jnp.int32(limit), self._key)
        self.host_syncs += 1
        with spans.span(spans.SYNC, chunk=chunk) as sp:
            v = int(ms.visits)
            spans.note(sp, visits=v)
        with spans.span(spans.HARVEST, chunk=chunk):
            # the mask reflects the chunk-end state even when v == 0
            # (megastep recomputes it from the unchanged input state); a
            # chunk that stops below its limit proves the device is
            # drained — no confirmation dispatch needed
            # both small planes in one round trip
            self._lane_pending, tallies = jax.device_get(
                (ms.lane_pending, ms.tallies))
            self._drained = v < limit
            if v == 0:
                return 0
            self.state = st
            self._key = ms.key
            self._edges += _visit.harvest_edges(ms.eq_hi, ms.eq_lo)
            counts = np.asarray(ms.visit_counts, dtype=np.int64)
        self.lane_visits += int(tallies[1])
        self.modeled_bytes += float(counts @ self.engine._visit_bytes)
        self.visits += v
        return v

    def pump(self, max_visits: int) -> int:
        """Advance up to ``max_visits`` visits in device-resident chunks of
        up to the engine's K; admission and harvest happen at the chunk
        boundaries (DESIGN.md §3.3).  Returns visits executed.

        Holds the executor lock per chunk, releasing it at every chunk
        boundary — exactly where foreign-thread submits are allowed in."""
        start = self.visits
        while True:
            with self._lock:
                if self.visits - start >= max_visits:
                    break
                self._admit()
                did = self._chunk(max_visits - (self.visits - start))
                with spans.span(spans.HARVEST):
                    self._harvest(pending=self._lane_pending)
                if did == 0 or self._drained:
                    # nothing left pending on device: every unfinished lane
                    # was just harvested; refill from the queue or stop
                    self._admit()
                    if not self.queue and self.active == 0:
                        break
        return self.visits - start

    def run(self, max_visits: Optional[int] = None) -> Dict[int, np.ndarray]:
        """Drain queue + lanes; returns {qid: values} (original ids)."""
        budget = max_visits or 2000 * self.bg.num_parts
        while (self.queue or self.active) and self.visits < budget:
            if self.pump(budget - self.visits) == 0:
                break
        with self._lock:
            self._harvest()
            return {qid: q.values
                    for qid, q in self.queries.items() if q.done}

    def result(self, qid: int) -> StreamQuery:
        return self.queries[qid]


class WalkExecutor:
    """Slot-recycling random-walk lanes: the :class:`StreamingExecutor`
    surface (submit / pump / run / take_finished / result) over the
    buffered walker loop (core/randomwalk.py).

    A lane holds one walker; free lanes park with ``steps = length`` so the
    jitted visit's liveness mask skips them.  Because the rw tape is keyed
    by (source, step) — never by lane, batch, or visit order — a walker
    admitted into a recycled slot mid-stream draws exactly the trajectory
    the one-shot ``session.run("rw", ...)`` would, so served occupancy rows
    are bitwise the session's.  ``length`` and ``seed`` are executor-wide
    (like ``alpha``/``eps`` on the push lanes): they parameterize the
    compiled visit, so requests wanting different values belong on a
    different executor.

    Completion is per-lane exact (``steps >= length``), values are
    occupancy counts [n] in original ids (start + each step), and
    ``edges`` bills the steps actually taken — the same contract
    ``backends.run_query("rw")`` returns.  Thread-safety matches
    StreamingExecutor: one lock, foreign submits join at visit boundaries.
    """

    def __init__(self, session, capacity: int = 16, *, length: int = 32,
                 seed: int = 0, k_visits: int = 64, visit=None):
        from repro.core.engine import DeviceGraph
        from repro.core.randomwalk import make_walk_visit
        from repro.core.yielding import NO_YIELD
        self.session = session
        self.kind = "rw"
        self.capacity = int(capacity)
        self.length, self.seed = int(length), int(seed)
        self.k_visits = int(k_visits)
        bg, perm = session.prepared()
        self.bg, self.perm = bg, perm
        self.dg = DeviceGraph.build(bg, NO_YIELD, self.capacity)
        # ``visit`` injects a warm AOT-compiled walk visit
        # (serve/compile_cache.build_warm_megastep kind="rw") — same
        # function of the same graph constants, so injection never changes
        # a trajectory
        self._visit = (visit if visit is not None
                       else make_walk_visit(self.dg, self.length, self.seed))
        B = self.dg.block_size
        # one visit streams the resident diagonal block plus every boundary
        # block against it — the same neighborhood the planner budgets
        self._visit_bytes = float(
            (1 + self.dg.nbr_blk.shape[1]) * B * B * 4)
        Q, n_pad = self.capacity, self.dg.num_parts * B
        self._pos = jnp.zeros(Q, jnp.int32)
        self._steps = jnp.full(Q, self.length, jnp.int32)   # parked
        self._part = jnp.zeros(Q, jnp.int32)
        self._src = jnp.zeros(Q, jnp.int32)
        self._thash = jnp.zeros(Q, jnp.uint32)
        self._occ = jnp.zeros((Q, n_pad), jnp.float32)
        self._lock = threading.RLock()
        self.finished: collections.deque = collections.deque()
        self.queue: collections.deque = collections.deque()
        self.queries: Dict[int, StreamQuery] = {}
        self.free_slots: List[int] = list(range(self.capacity))
        self.slot_qid = np.full(self.capacity, -1, dtype=np.int64)
        self.visits = 0
        self.modeled_bytes = 0.0
        self.host_syncs = 0
        self._next_qid = 0

    # ----------------------------------------------------------- admission

    def submit(self, sources: np.ndarray) -> List[int]:
        """Enqueue walk sources (original ids); returns their qids."""
        with self._lock:
            qids = []
            for s in np.atleast_1d(np.asarray(sources)):
                q = StreamQuery(qid=self._next_qid, source=int(s),
                                submitted_visit=self.visits)
                self._next_qid += 1
                self.queries[q.qid] = q
                self.queue.append(q.qid)
                qids.append(q.qid)
            self._admit()
            return qids

    def _admit(self):
        B = self.dg.block_size
        while self.free_slots and self.queue:
            qid = self.queue.popleft()
            slot = self.free_slots.pop(0)
            q = self.queries[qid]
            src = int(self.perm[q.source])
            # identical to randomwalk.init_walk_state, per lane
            self._pos = self._pos.at[slot].set(src)
            self._steps = self._steps.at[slot].set(0)
            self._part = self._part.at[slot].set(src // B)
            self._src = self._src.at[slot].set(src)
            self._thash = self._thash.at[slot].set(jnp.uint32(src))
            self._occ = self._occ.at[slot].set(0.0).at[slot, src].set(1.0)
            q.slot = slot
            q.admitted_visit = self.visits
            q.admitted_sync = self.host_syncs
            self.slot_qid[slot] = q.qid

    # ------------------------------------------------------------- harvest

    def _harvest(self):
        active = self.slot_qid >= 0
        if not active.any():
            return
        self.host_syncs += 1
        steps = np.asarray(self._steps)
        done = active & (steps >= self.length)
        if not done.any():
            return
        occ = np.asarray(self._occ)
        n = self.bg.n
        for slot in np.flatnonzero(done):
            q = self.queries[int(self.slot_qid[slot])]
            q.values = occ[slot, :n][self.perm].astype(np.float32)
            q.edges = float(steps[slot])
            q.finished_visit = self.visits
            q.finished_sync = self.host_syncs
            q.done = True
            self.finished.append(q.qid)
            self.slot_qid[slot] = -1
            self.free_slots.append(int(slot))
            # park the lane; its occupancy row resets at the next admit
            self._steps = self._steps.at[int(slot)].set(self.length)

    # ---------------------------------------------------------------- loop

    @property
    def active(self) -> int:
        return int((self.slot_qid >= 0).sum())

    @property
    def queue_depth(self) -> int:
        return len(self.queue)

    def take_finished(self) -> List[int]:
        with self._lock:
            out = list(self.finished)
            self.finished.clear()
            return out

    def pump(self, max_visits: int) -> int:
        """Advance up to ``max_visits`` buffered walk visits, admitting and
        harvesting around each (walk scheduling reads walker residency from
        the host every visit, so per-visit boundaries cost no extra sync).
        Returns visits executed."""
        start = self.visits
        while True:
            with self._lock:
                if self.visits - start >= int(max_visits):
                    break
                self._admit()
                self.host_syncs += 1
                steps = np.asarray(self._steps)
                part = np.asarray(self._part)
                live = (self.slot_qid >= 0) & (steps < self.length)
                if not live.any():
                    self._harvest()
                    self._admit()
                    if not self.queue and self.active == 0:
                        break
                    continue    # freshly admitted (or length-0) lanes
                # max-ops scheduling: the partition with most live walkers
                counts = np.bincount(part[live],
                                     minlength=self.dg.num_parts)
                p = int(np.argmax(counts))
                (self._pos, self._steps, self._part, self._thash,
                 self._occ) = self._visit(self._pos, self._steps,
                                          self._part, self._src,
                                          self._thash, self._occ,
                                          jnp.int32(p))
                self.visits += 1
                self.modeled_bytes += self._visit_bytes
                self._harvest()
        return self.visits - start

    def run(self, max_visits: Optional[int] = None) -> Dict[int, np.ndarray]:
        """Drain queue + lanes; returns {qid: occupancy} (original ids)."""
        budget = max_visits or 2000 * self.bg.num_parts
        while (self.queue or self.active) and self.visits < budget:
            if self.pump(budget - self.visits) == 0:
                break
        with self._lock:
            self._harvest()
            return {qid: q.values
                    for qid, q in self.queries.items() if q.done}

    def result(self, qid: int) -> StreamQuery:
        return self.queries[qid]
