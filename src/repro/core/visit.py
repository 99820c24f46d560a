"""The visit algebra — one Algorithm-2 skeleton for every runtime and mode.

The paper's Algorithm 2 is a single shape regardless of query family:

    apply buffered ops   (consolidate into the resident partition's state)
    relax locally        (until converged, yielded, or out of budget)
    emit boundary ops    (one contribution per neighbor partition)

The repo used to hand-write that skeleton three times (minplus visit, push
visit, distributed minplus superstep) and the copies drifted — the push family
never reached the pod runtime.  This module factors the *mode-specific*
operators into a :class:`VisitAlgebra` and keeps exactly three generic drivers:

  :func:`make_visit`     the single-device visit kernel (one visit per dispatch)
  :func:`make_megastep`  K visits per host dispatch: partition selection is an
                         on-device argmin/argmax over the ``[P]`` metadata
                         planes and the visit body runs in a ``lax.while_loop``
                         (DESIGN.md §2.3) — the engine's hot loop
  :func:`superstep`      the per-device superstep body (``shard_map`` runtime)

Both are instantiated twice — :func:`minplus_algebra` (SSSP/BFS/BC/LL: buffer
combines by ``min``, relax is a tropical matmul) and :func:`push_algebra`
(PPR/NCP: buffer combines by ``+``, relax is a masked residual push).  Any
future mode (weighted PPR variants, reachability, k-hop sketches) lands in
*both* runtimes by defining one more operator set here (DESIGN.md §2.1).

Edge accounting is integral on device (int32 per visit — a visit touches far
fewer than 2^31 edges per query) and accumulated on host in float64, so counts
stay exact past float32's 2^24 integer ceiling on paper-scale graphs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.minplus import ops as minplus_ops

INF = jnp.inf
_BIG_STAMP = np.iinfo(np.int32).max - 1
_INT32_MAX = np.iinfo(np.int32).max

#: distributed edge counters carry (hi, lo) int32 lanes; lo spills into hi in
#: units of 2**_EDGE_SHIFT so totals stay exact up to ~2^51 edges per query.
EDGE_SHIFT = 20


# ---------------------------------------------------------------------------
# algebra: the mode-specific operators of Algorithm 2


class MinplusCarry(NamedTuple):
    d: jax.Array        # [Q, B] tentative values
    pending: jax.Array  # [Q, B] ops not yet relaxed this visit
    emit: jax.Array     # [Q, B] rows relaxed this visit (emission sources)
    alpha: jax.Array    # [Q, 1] best applied value (Δ-window anchor)


class PushCarry(NamedTuple):
    p: jax.Array        # [Q, B] PPR mass
    r: jax.Array        # [Q, B] residual (buffered ops consolidated in)
    acc: jax.Array      # [Q, B] accumulated pushed mass (emission payload)


class EllOps(NamedTuple):
    """An algebra's ``step`` and ``contrib`` over a block's pull-ELL slice
    ``(src [B, W], w [B, W])`` (``BlockGraph.ell_src`` / ``ell_w``) in
    place of its dense ``[B, B]`` tile; same signatures otherwise."""
    step: Callable
    contrib: Callable


@dataclasses.dataclass(frozen=True)
class VisitAlgebra:
    """Mode-specific operators; everything else is the shared skeleton.

    Conventions: ``planes`` is a tuple of ``[..., Q, B]`` value planes —
    ``(dist,)`` for minplus, ``(p, r)`` for push.  ``deg`` broadcasts on the
    last axis (``[B]`` per-partition row or ``[P, B]`` full), so ``pending``
    works on both a single resident partition and a whole device shard.
    """
    name: str
    identity: float                  # empty-buffer cell (+inf / 0)
    source_value: float              # buffered op injected per query source
    plane_init: Tuple[float, ...]    # initial plane fill values
    combine: Callable                # consolidate ops: (buf, contrib) -> buf
    begin: Callable                  # (planes_row, buf_row, deg_row) -> carry
    active: Callable                 # (carry, deg_row, eq, budget) -> [Q, B]
    step: Callable                   # (carry, active, w_pp, deg_row) -> carry
    emit_payload: Callable           # (carry) -> [Q, B] boundary payload
    emit_mask: Callable              # (carry) -> [Q, B] rows that cost edges
    contrib: Callable                # (payload, w_pj) -> [Q, B] neighbor ops
    scatter: Callable                # (buf, idx [S], cands [S, Q, B]) -> buf;
    #                                  segment-combine: duplicate idx entries
    #                                  fold by ``combine`` (min / add)
    pending: Callable                # (buf, planes, deg) -> bool [..., Q, B]
    prio_of: Callable                # (buf_row, planes_row, deg_row)
    #                                  -> (f32 priority, i32 op count)
    finish: Callable                 # (carry, deg_row) -> (planes_row', keep)
    #: the scalar hyperparameters the operators closed over, as static data —
    #: the fused Pallas visit kernel rebuilds the inner-round math from these
    #: (kernel bodies can't call back into the closure-captured XLA ops).
    params: Tuple[Tuple[str, float], ...] = ()
    #: the same operators over pull-ELL slices; None where the algebra
    #: contracts dense tiles only.  The XLA visit body reads the view when
    #: this is set and the graph has one (:func:`uses_ell`).
    ell: Optional[EllOps] = None

    @property
    def num_planes(self) -> int:
        return len(self.plane_init)

    def param(self, name: str) -> float:
        return dict(self.params)[name]


def minplus_algebra(window: float, relax: Optional[Callable] = None,
                    strict: bool = False) -> VisitAlgebra:
    """SSSP/BFS family: ops combine by ``min``, relax is min-plus matmul.

    With the default XLA ``relax`` the algebra also relaxes over pull-ELL
    slices (``ell``, through ``kernels/minplus/ops.minplus_ell``,
    bit-identical); a caller's own dense ``relax`` (the Pallas kernel)
    is kept for every block, so such an algebra has no ``ell``.

    ``strict=True`` makes an op pend only when it *strictly* improves the
    plane value (``buf < d`` instead of ``buf <= d``).  Positive-weight
    kinds terminate either way — a cycle re-sends values strictly above
    the plane, so equal re-sends never happen — but the zero-weight cc
    instantiation livelocks under ``<=``: two partitions forever re-emit
    each other's already-applied labels (equal, hence pending, hence
    re-emitted).  Strictness drops an op that cannot change anything,
    which is exact for an idempotent min fixpoint.
    """
    dense_only = relax is not None
    relax = relax or minplus_ops.minplus
    lt = jnp.less if strict else jnp.less_equal

    def pending(buf, planes, deg):
        (d,) = planes
        return jnp.isfinite(buf) & lt(buf, d)

    def prio_of(buf_row, planes_row, deg_row):
        pend = pending(buf_row, planes_row, deg_row)
        return (jnp.min(jnp.where(pend, buf_row, INF)),
                jnp.sum(pend, dtype=jnp.int32))

    def begin(planes_row, buf_row, deg_row):
        (d0,) = planes_row
        pending0 = jnp.isfinite(buf_row) & lt(buf_row, d0)
        d1 = jnp.minimum(d0, jnp.where(pending0, buf_row, INF))
        alpha = jnp.min(jnp.where(pending0, d1, INF), axis=1, keepdims=True)
        return MinplusCarry(d=d1, pending=pending0,
                            emit=jnp.zeros_like(pending0), alpha=alpha)

    def active(carry, deg_row, eq, budget):
        return (carry.pending & (carry.d <= carry.alpha + window)
                & (eq.astype(jnp.float32) < budget)[:, None])

    def step_over(contract):
        def step(carry, act, tile, deg_row):
            srcs = jnp.where(act, carry.d, INF)
            nd = contract(srcs, tile)
            improved = nd < carry.d
            return MinplusCarry(d=jnp.minimum(carry.d, nd),
                                pending=(carry.pending & ~act) | improved,
                                emit=carry.emit | act, alpha=carry.alpha)
        return step

    ell = None
    if not dense_only:
        def contrib_ell(x, tile):
            return minplus_ops.minplus_ell(x, *tile)
        ell = EllOps(step=step_over(contrib_ell), contrib=contrib_ell)

    def finish(carry, deg_row):
        keep = jnp.where(carry.pending, carry.d, INF)
        return (carry.d,), keep

    return VisitAlgebra(
        name="minplus", identity=float(np.inf), source_value=0.0,
        plane_init=(float(np.inf),), combine=jnp.minimum,
        begin=begin, active=active, step=step_over(relax),
        emit_payload=lambda carry: jnp.where(carry.emit, carry.d, INF),
        emit_mask=lambda carry: carry.emit,
        contrib=relax,
        scatter=lambda buf, idx, cands: buf.at[idx].min(cands),
        pending=pending, prio_of=prio_of, finish=finish,
        params=(("window", float(window)),
                ("strict", 1.0 if strict else 0.0)),
        ell=ell)


def push_algebra(alpha: float, eps: float,
                 spread: Optional[Callable] = None) -> VisitAlgebra:
    """PPR family: residual contributions combine by ``+``, relax is a masked
    ACL push round, priority is the most negative residual ratio."""
    spread = spread or minplus_ops.masked_matmul

    def _thresh(deg):
        return eps * jnp.maximum(deg, 1).astype(jnp.float32)

    def pending(buf, planes, deg):
        _, r = planes
        return (((r + buf) >= _thresh(deg)[..., None, :])
                & (deg > 0)[..., None, :])

    def prio_of(buf_row, planes_row, deg_row):
        _, r = planes_row
        ratio = (r + buf_row) / _thresh(deg_row)[None, :]
        has_edges = (deg_row > 0)[None, :]
        ready = (ratio >= 1.0) & has_edges
        prio = jnp.where(jnp.any(ready),
                         -jnp.max(jnp.where(has_edges, ratio, -INF)), INF)
        return prio, jnp.sum(ready, dtype=jnp.int32)

    def begin(planes_row, buf_row, deg_row):
        p0, r0 = planes_row
        return PushCarry(p=p0, r=r0 + buf_row, acc=jnp.zeros_like(r0))

    def active(carry, deg_row, eq, budget):
        return ((carry.r >= _thresh(deg_row)[None, :])
                & (deg_row > 0)[None, :]
                & (eq.astype(jnp.float32) < budget)[:, None])

    def step(carry, act, w_pp, deg_row):
        degc = jnp.maximum(deg_row, 1).astype(jnp.float32)
        af = act.astype(carry.r.dtype)
        pushed = (1.0 - alpha) * carry.r * af / degc[None, :]
        return PushCarry(p=carry.p + alpha * carry.r * af,
                         r=carry.r * (1.0 - af) + spread(pushed, w_pp),
                         acc=carry.acc + pushed)

    def finish(carry, deg_row):
        return (carry.p, carry.r), jnp.zeros_like(carry.r)

    return VisitAlgebra(
        name="push", identity=0.0, source_value=1.0, plane_init=(0.0, 0.0),
        combine=lambda buf, contrib: buf + contrib,
        begin=begin, active=active, step=step,
        emit_payload=lambda carry: carry.acc,
        emit_mask=lambda carry: carry.acc > 0,
        contrib=spread,
        scatter=lambda buf, idx, cands: buf.at[idx].add(cands),
        pending=pending, prio_of=prio_of, finish=finish,
        params=(("alpha", float(alpha)), ("eps", float(eps))))


# ---------------------------------------------------------------------------
# shared state container + initialization


class VisitState(NamedTuple):
    """Engine-side buffered state; the algebra defines what the planes mean."""
    planes: Tuple[jax.Array, ...]  # mode value planes, each [P, Q, B]
    buf: jax.Array                 # [P+1, Q, B] pending ops (row P = trash)
    prio: jax.Array                # [P] best pending priority (+inf empty)
    ops_count: jax.Array           # [P] pending op count
    stamp: jax.Array               # [P] visit counter when buf became non-empty


def init_dense_state(algebra: VisitAlgebra, num_parts: int, num_queries: int,
                     block_size: int, sources: np.ndarray,
                     trash_row: bool = True,
                     init_ops: Optional[np.ndarray] = None):
    """Host-side (planes, buf) with one source op buffered per query lane.

    ``sources``: [k] reordered vertex ids, k <= num_queries — lane ``i`` gets
    ``sources[i]``; remaining lanes start empty (streaming admission fills
    them later by the exact same buffered-op injection).

    ``init_ops``: optional ``[P, B]`` plane of buffered ops broadcast to
    every query lane before source injection — the every-vertex-is-a-source
    kinds (cc label propagation seeds each vertex with its own label) start
    from this instead of a one-hot source.  Cells holding
    ``algebra.identity`` stay empty, so partition padding is expressed by
    the caller writing identity there.
    """
    P, Q, B = num_parts, num_queries, block_size
    planes = tuple(np.full((P, Q, B), v, dtype=np.float32)
                   for v in algebra.plane_init)
    buf = np.full((P + (1 if trash_row else 0), Q, B), algebra.identity,
                  dtype=np.float32)
    if init_ops is not None:
        buf[:P] = np.broadcast_to(
            np.asarray(init_ops, dtype=np.float32)[:, None, :], (P, Q, B))
    sources = np.asarray(sources)
    if sources.size:
        parts, locs = np.divmod(sources, B)
        buf[parts, np.arange(sources.size), locs] = algebra.source_value
    return planes, buf


def cc_label_plane(bg) -> np.ndarray:
    """[P, B] initial cc label ops: every real vertex seeds its own reordered
    id as an f32 minplus op; padding slots hold the identity (+inf).  Shared
    by every cc backend so the propagated fixpoint is the same plane bitwise
    (integer-valued f32 mins, exact below 2^24 vertices)."""
    P, B = bg.num_parts, bg.block_size
    ids = np.arange(P * B, dtype=np.float32).reshape(P, B)
    return np.where(np.asarray(bg.vmask), ids, np.float32(np.inf))


def state_meta(algebra: VisitAlgebra, planes, buf, deg, counter: int = 0):
    """(prio, ops_count, stamp) for every partition, from the algebra's own
    priority operator — the single source of scheduling truth."""
    P = deg.shape[0]
    prio, ops = jax.vmap(algebra.prio_of)(buf[:P], planes, deg)
    stamp = jnp.where(jnp.isfinite(prio), jnp.int32(counter),
                      jnp.int32(_BIG_STAMP))
    return prio, ops, stamp


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _fresh_dense_state(plane_init, identity, source_value, shape, parts,
                       lanes, locs):
    """:func:`init_dense_state` (trash row included, no ``init_ops``) made
    on the device: only the sources' positions cross from the host."""
    P, Q, B = shape
    planes = tuple(jnp.full((P, Q, B), v, jnp.float32) for v in plane_init)
    buf = jnp.full((P + 1, Q, B), identity, jnp.float32)
    return planes, buf.at[parts, lanes, locs].set(source_value)


def init_engine_state(algebra: VisitAlgebra, dg, sources: np.ndarray,
                      num_queries: Optional[int] = None,
                      init_ops: Optional[np.ndarray] = None) -> VisitState:
    """Device state for the host-scheduled engine (trash buffer row included).

    Without ``init_ops`` the planes and buffer are filled on the device,
    bit for bit :func:`init_dense_state`'s."""
    Q = int(num_queries if num_queries is not None else len(sources))
    if init_ops is None:
        parts, locs = np.divmod(np.asarray(sources, dtype=np.int64),
                                dg.block_size)
        planes, buf = _fresh_dense_state(
            tuple(float(v) for v in algebra.plane_init),
            float(algebra.identity), float(algebra.source_value),
            (dg.num_parts, Q, dg.block_size), parts.astype(np.int32),
            np.arange(parts.size, dtype=np.int32), locs.astype(np.int32))
    else:
        planes_np, buf_np = init_dense_state(
            algebra, dg.num_parts, Q, dg.block_size, sources, trash_row=True,
            init_ops=init_ops)
        planes = tuple(jnp.asarray(x) for x in planes_np)
        buf = jnp.asarray(buf_np)
    prio, ops, stamp = state_meta(algebra, planes, buf, dg.deg)
    return VisitState(planes, buf, prio, ops, stamp)


# ---------------------------------------------------------------------------
# generic visit kernel (single-device engine)


def uses_ell(dg, algebra: VisitAlgebra) -> bool:
    """Whether the XLA visit body relaxes over the graph's pull-ELL view:
    the algebra has an ELL form and the graph has the view (its widest
    column is small against B, ``core/graph.ELL_MAX_WIDTH_SHARE``)."""
    return algebra.ell is not None and dg.ell_width > 0


def relax_width(dg, algebra: VisitAlgebra) -> int:
    """Candidates the XLA visit body's relaxation reduces over per output:
    ``W`` over the pull-ELL view, ``B`` over the dense tile."""
    return dg.ell_width if uses_ell(dg, algebra) else dg.block_size


def _make_visit_body(dg, algebra: VisitAlgebra, max_rounds: int) -> Callable:
    """The unjitted visit body (Alg. 2 lines 6-16): apply + relax until
    yield, then emit one combined contribution per neighbor partition.

    ``visit(state, p, counter) -> (state', (rounds, eq))`` where ``eq`` is
    this visit's per-query edge count (int32 [Q], exact).  :func:`make_visit`
    jits it for per-visit host dispatch; :func:`make_megastep` runs it inside
    a device-resident ``lax.while_loop``.

    The relaxation and the emission contract either each block's dense
    ``[B, B]`` tile or its pull-ELL slice (:func:`uses_ell`); the bits are
    the same either way.
    """
    P = dg.num_parts

    def slice_of(store, k):
        # one dynamic slice per block: a gather over the block store lets
        # XLA split (and so copy) the whole store inside the visit loop
        return jax.lax.dynamic_index_in_dim(store, k, keepdims=False)

    if uses_ell(dg, algebra):
        step, contrib = algebra.ell

        def block(k):
            return slice_of(dg.ell_src, k), slice_of(dg.ell_w, k)
    else:
        step, contrib = algebra.step, algebra.contrib

        def block(k):
            return slice_of(dg.blocks, k)

    def visit(state: VisitState, p: jax.Array, counter: jax.Array):
        # the scopes name the visit's phases in the compiled program's op
        # metadata (``visit/relax`` ...), so a device trace reads by phase
        kd = dg.diag_blk[p]
        w_pp, nnz_pp, deg_p = block(kd), dg.row_nnz[kd], dg.deg[p]
        budget = dg.edge_budget[p]
        with jax.named_scope("visit/apply"):
            planes_row = tuple(x[p] for x in state.planes)
            buf_row = state.buf[p]
            carry0 = algebra.begin(planes_row, buf_row, deg_p)

        def cond(c):
            carry, eq, rounds = c
            return jnp.logical_and(
                rounds < max_rounds,
                jnp.any(algebra.active(carry, deg_p, eq, budget)))

        def body(c):
            carry, eq, rounds = c
            act = algebra.active(carry, deg_p, eq, budget)
            eq = eq + jnp.sum(jnp.where(act, nnz_pp[None, :], 0), axis=1,
                              dtype=jnp.int32)
            return step(carry, act, w_pp, deg_p), eq, rounds + 1

        eq0 = jnp.zeros(buf_row.shape[0], dtype=jnp.int32)
        with jax.named_scope("visit/relax"):
            carry, eq, rounds = jax.lax.while_loop(
                cond, body, (carry0, eq0, jnp.int32(0)))
        with jax.named_scope("visit/emit"):
            state, eq = emit(state, p, counter, carry, eq)
        with jax.named_scope("visit/writeback"):
            state = writeback(state, p, counter, carry, deg_p)
        return state, (rounds, eq)

    def emit(state: VisitState, p, counter, carry, eq):
        # ---- emission to neighbor partitions (Alg. 2 line 16): ONE batched
        # contrib over all neighbor blocks (vmap) + a single segment-combine
        # scatter, instead of a serial dmax-step fori_loop ----
        payload = algebra.emit_payload(carry)
        emask = algebra.emit_mask(carry)
        parts = dg.nbr_part[p]                         # [dmax] (-1 pad)
        valid = parts >= 0
        blk0 = jnp.where(valid, dg.nbr_blk[p], 0)
        j0 = jnp.where(valid, parts, 0)                # clamped gather index
        jj = jnp.where(valid, parts, P)                # trash row for padding
        w_nb = jax.tree.map(lambda *t: jnp.stack(t),
                            *[block(blk0[s]) for s in range(blk0.shape[0])])
        cands = jax.vmap(lambda w: contrib(payload, w))(
            w_nb)                                      # [dmax, Q, B]
        cands = jnp.where(valid[:, None, None], cands, algebra.identity)
        nnz_sl = jnp.where(valid[:, None], dg.row_nnz[blk0], 0)  # [dmax, B]
        eq = eq + jnp.sum(jnp.where(emask[None], nnz_sl[:, None, :], 0),
                          axis=(0, 2), dtype=jnp.int32)
        was_empty = ~jnp.isfinite(state.prio)          # [P], pre-emission
        buf = algebra.scatter(state.buf, jj, cands)
        # metadata refresh gathers AFTER the full scatter, so duplicate
        # destinations all observe the combined row (order-independent)
        planes_j = tuple(x[j0] for x in state.planes)
        newprio, newops = jax.vmap(algebra.prio_of)(buf[j0], planes_j,
                                                    dg.deg[j0])
        prio = state.prio.at[jj].set(newprio, mode="drop")
        ops_count = state.ops_count.at[jj].set(newops, mode="drop")
        stamp = state.stamp.at[jj].set(
            jnp.where(was_empty[j0] & jnp.isfinite(newprio), counter,
                      state.stamp[j0]), mode="drop")
        return state._replace(buf=buf, prio=prio, ops_count=ops_count,
                              stamp=stamp), eq

    def writeback(state: VisitState, p, counter, carry, deg_p):
        # ---- write back own planes, keep yielded ops, refresh priority ----
        new_rows, keep_row = algebra.finish(carry, deg_p)
        buf = state.buf.at[p].set(keep_row)
        own_prio, own_ops = algebra.prio_of(keep_row, new_rows, deg_p)
        prio = state.prio.at[p].set(own_prio)
        ops_count = state.ops_count.at[p].set(own_ops)
        stamp = state.stamp.at[p].set(jnp.where(jnp.isfinite(own_prio),
                                                counter,
                                                jnp.int32(_BIG_STAMP)))
        planes = tuple(x.at[p].set(nr)
                       for x, nr in zip(state.planes, new_rows))
        return VisitState(planes, buf, prio, ops_count, stamp)

    return visit


class GraphBound:
    """A jitted program with the device graph bound as its first argument.

    The graph's arrays ride as operands, never as constants closed over by
    the trace: a closed-over array is embedded in the program text, which
    at road-network size is gigabytes of HLO.  Calling, ``lower(...)`` and
    ``lower(...).compile()`` all take the remaining arguments only, so a
    bound program and its AOT-compiled executable share one calling
    convention.

    ``traces`` counts how often the program's Python body ran, that is,
    was traced (a program that counts its traces bumps it from its body).
    """

    def __init__(self, fn, graph):
        self.fn = fn
        self.graph = graph
        self.traces = 0

    def __call__(self, *args):
        return self.fn(self.graph, *args)

    def lower(self, *args):
        return _BoundLowered(self.fn.lower(self.graph, *args), self.graph)

    def __getattr__(self, name):
        return getattr(self.__dict__["fn"], name)


class _BoundLowered:
    def __init__(self, lowered, graph):
        self.lowered = lowered
        self.graph = graph

    def compile(self) -> GraphBound:
        return GraphBound(self.lowered.compile(), self.graph)

    def __getattr__(self, name):
        return getattr(self.__dict__["lowered"], name)


def make_visit(dg, algebra: VisitAlgebra, max_rounds: int) -> Callable:
    """The one visit kernel, jitted for per-visit host dispatch.

    ``visit(state, p, counter) -> (state', (rounds, eq))``.
    """
    @jax.jit
    def visit(g, state, p, counter):
        return _make_visit_body(g, algebra, max_rounds)(state, p, counter)

    return GraphBound(visit, dg)


# ---------------------------------------------------------------------------
# device-resident scheduling: the K-visit megastep (DESIGN.md §2.3)


def device_select(policy: str, prio: jax.Array, stamp: jax.Array,
                  ops_count: jax.Array, key: jax.Array) -> jax.Array:
    """On-device mirror of ``PartitionScheduler.select`` (the host oracle).

    Returns the selected partition index (i32 scalar); the caller guarantees
    at least one finite-priority partition (the megastep's while-cond).  The
    deterministic policies reproduce the host argmin/argmax bit-for-bit,
    including first-index tie-breaking; ``random`` draws a uniform per
    partition from the carried threefry ``key`` and argmaxes it over the
    non-empty set — a uniform choice, seeded and replayable on device (the
    host scheduler's numpy ``Generator`` stream differs, but scheduling
    never changes results, paper §5.1).
    """
    if policy == "priority":
        return jnp.argmin(prio)
    nonempty = jnp.isfinite(prio)
    if policy == "fifo":
        return jnp.argmin(jnp.where(nonempty, stamp, jnp.int32(_INT32_MAX)))
    if policy == "max_ops":
        return jnp.argmax(jnp.where(nonempty, ops_count, jnp.int32(-1)))
    if policy == "random":
        u = jax.random.uniform(key, prio.shape)
        return jnp.argmax(jnp.where(nonempty, u, -1.0))
    raise ValueError(f"unknown scheduling policy {policy!r}")


class MegastepStats(NamedTuple):
    """Per-chunk device accumulators, harvested once per host dispatch."""
    visits: jax.Array        # i32 scalar: visits executed this chunk (<= K)
    tallies: jax.Array       # [2] i32: total relaxation rounds, and lane
    #                          visits (query lanes that processed an edge,
    #                          summed over the visits); one read for both
    eq_hi: jax.Array         # [Q] i32: per-query edge count, high lane
    eq_lo: jax.Array         # [Q] i32: low lane (< 2**EDGE_SHIFT)
    visit_counts: jax.Array  # [P] i32: visits per partition (traffic model)
    order: jax.Array         # [K] i32 visit-order ring (-1 = unused slot)
    lane_pending: jax.Array  # [Q] bool: query lane still has a pending op
    #                          anywhere (streaming harvest, same dispatch)
    key: jax.Array           # threefry key to carry into the next chunk


def make_megastep(dg, algebra: VisitAlgebra, max_rounds: int,
                  policy: str = "priority", K: int = 64,
                  harvest_mask: bool = False, fused: bool = False,
                  frontier_mode: str = "dense") -> Callable:
    """Device-resident scheduling loop: up to K visits per host dispatch.

    Wraps the visit body in a ``lax.while_loop`` whose scheduler decision is
    an on-device argmin/argmax over the ``[P]`` prio/stamp/ops planes the
    visit kernel already maintains (``random`` draws from a threefry key
    carried in the loop), so the host is consulted once per K visits instead
    of once per visit.  Per-visit stats accumulate on device
    (:class:`MegastepStats`) and are harvested once per chunk; the edge
    counters carry an exact ``(hi, lo)`` int32 pair per query (lo spills
    into hi in 2**EDGE_SHIFT units, the distributed-runtime idiom).

    Returns ``megastep(state, counter, limit, key) -> (state', stats)``:
    ``counter`` is the global visit counter at chunk start (stamps continue
    across chunks), ``limit`` dynamically caps this chunk at
    ``min(limit, K)`` visits (exact ``max_visits`` semantics without a
    recompile), and the loop exits early when no partition holds a pending
    op — ``stats.visits < limit`` is the host's termination signal.

    ``harvest_mask=True`` additionally reduces the per-query pending-lane
    mask from the chunk-end state into ``stats.lane_pending`` — the
    streaming executor's harvest rides the same dispatch.  Plain engine
    runs never read it, so they skip the [P, Q, B] reduction (the field is
    an empty placeholder).

    ``fused=True`` swaps the visit body for the fused Pallas kernel
    (``kernels/fused_visit``): the resident partition's planes, buffer
    row, and scheduler metadata stay in VMEM for the whole visit, with
    ``kernels/frontier`` (consolidation) and ``kernels/ppr_push`` (push
    rounds) as the in-kernel tile ops.  Bit-identical to the XLA body for
    minplus and deterministic push (see ``kernels/fused_visit/fused.py``
    for the parity argument; ``tests/test_fused_visit.py`` pins it).
    ``frontier_mode="sparse"`` (minplus only) makes the in-kernel relax
    skip all-inf source chunks — identical bits, less work on the thin
    late-round frontiers (DESIGN.md §2.4).
    """
    from repro.core.scheduler import POLICIES
    if policy not in POLICIES:
        raise ValueError(f"unknown scheduling policy {policy!r}; "
                         f"one of {POLICIES}")
    if K < 1:
        raise ValueError(f"megastep chunk size K must be >= 1, got {K}")
    P = dg.num_parts
    if fused:
        # the dispatch-table wiring of the three visit kernels (the
        # pallas.reachability pass keys off these imports)
        from repro.kernels.frontier.ops import frontier_tile
        from repro.kernels.fused_visit.ops import make_fused_visit
        from repro.kernels.ppr_push.ops import push_tile
        fv = make_fused_visit(dg, algebra, max_rounds,
                              frontier=frontier_tile, push=push_tile,
                              frontier_mode=frontier_mode)

        # the while_loop carries the kernel's packed layout for the whole
        # K-visit chunk: pack once on entry, unpack once on exit, and read
        # the scheduler metadata straight out of the packed tables.
        def visit_of(g):
            fg = fv.graph(g)

            def visit(pk, p, counter):
                pk, rounds, eq = fv.visit(fg, pk, p, counter)
                return pk, (rounds, eq)
            return visit

        def enter(state: VisitState):
            return fv.pack(state.planes, state.buf, state.prio,
                           state.ops_count, state.stamp)

        def leave(pk) -> VisitState:
            return VisitState(*fv.unpack(pk))

        def meta(pk):
            return pk.prio[:P], pk.stamp[:P], pk.ops[:P]
    else:
        if frontier_mode != "dense":
            raise ValueError(
                "frontier_mode is a fused-kernel switch; the XLA megastep "
                "always runs the dense frontier math")

        def visit_of(g):
            return _make_visit_body(g, algebra, max_rounds)
        enter = leave = lambda st: st

        def meta(st: VisitState):
            return st.prio, st.stamp, st.ops_count

    @jax.jit
    def megastep(g, state: VisitState, counter: jax.Array,
                 limit: jax.Array, key: jax.Array):
        bound.traces += 1               # trace time only: counts retraces
        visit = visit_of(g)
        limit_k = jnp.minimum(jnp.int32(limit), jnp.int32(K))

        def cond(c):
            st, k = c[0], c[1]
            return jnp.logical_and(k < limit_k,
                                   jnp.any(jnp.isfinite(meta(st)[0])))

        def body(c):
            st, k, tallies, hi, lo, counts, order, key = c
            if policy == "random":          # trace-time: only the random
                key, sub = jax.random.split(key)  # policy consumes entropy
            else:
                sub = key
            with jax.named_scope("select"):
                prio, stamp, ops_count = meta(st)
                p = device_select(policy, prio, stamp, ops_count, sub)
            st, (r, eq) = visit(st, p, counter + k)
            lo = lo + eq
            spill = lo >> EDGE_SHIFT
            hi = hi + spill
            lo = lo - (spill << EDGE_SHIFT)
            counts = counts.at[p].add(1)
            order = order.at[k].set(p.astype(jnp.int32))
            # once per visit, from the visit's own per-query edge counts
            tallies = tallies + jnp.stack(
                [r, jnp.sum(eq > 0, dtype=jnp.int32)])
            return st, k + 1, tallies, hi, lo, counts, order, key

        Q = state.buf.shape[1]
        init = (enter(state), jnp.int32(0), jnp.zeros(2, jnp.int32),
                jnp.zeros(Q, jnp.int32), jnp.zeros(Q, jnp.int32),
                jnp.zeros(P, jnp.int32), jnp.full((K,), -1, jnp.int32), key)
        st, k, tallies, hi, lo, counts, order, key = jax.lax.while_loop(
            cond, body, init)
        st = leave(st)
        with jax.named_scope("harvest_mask"):
            lane_pending = (jnp.any(
                algebra.pending(st.buf[:P], st.planes, g.deg), axis=(0, 2))
                if harvest_mask else jnp.zeros((0,), dtype=bool))
        return st, MegastepStats(visits=k, tallies=tallies, eq_hi=hi,
                                 eq_lo=lo, visit_counts=counts, order=order,
                                 lane_pending=lane_pending, key=key)

    bound = GraphBound(megastep, dg)
    return bound


def harvest_edges(eq_hi: np.ndarray, eq_lo: np.ndarray) -> np.ndarray:
    """Fold a harvested (hi, lo) int32 pair into exact float64 edge counts."""
    return (np.asarray(eq_hi, dtype=np.float64) * float(1 << EDGE_SHIFT)
            + np.asarray(eq_lo, dtype=np.float64))


# ---------------------------------------------------------------------------
# generic superstep (shard_map pod runtime)


def superstep(blocks, dstp, nnz, deg, budget, planes, buf, *,
              algebra: VisitAlgebra, max_rounds: int, pl: int, dmax: int,
              ndev: int, model_axis: str):
    """One superstep on one device's shard: visit the locally best-priority
    partition, then exchange boundary ops with a single ``all_to_all``.

    planes/buf: [pl, Qs, B].  Returns (planes', buf', eq int32 [Qs]).
    """
    prio, _ = jax.vmap(algebra.prio_of)(buf, planes, deg)
    p = jnp.argmin(prio)                  # all-INF => a harmless no-op visit

    w_all, nnz_all, deg_p = blocks[p], nnz[p], deg[p]
    w_pp, nnz_pp = w_all[0], nnz_all[0]
    planes_row = tuple(x[p] for x in planes)
    buf_row = buf[p]
    carry0 = algebra.begin(planes_row, buf_row, deg_p)
    budget_p = budget[p]
    Qs, B = buf_row.shape

    def cond(c):
        carry, eq, rounds = c
        return jnp.logical_and(
            rounds < max_rounds,
            jnp.any(algebra.active(carry, deg_p, eq, budget_p)))

    def body(c):
        carry, eq, rounds = c
        act = algebra.active(carry, deg_p, eq, budget_p)
        eq = eq + jnp.sum(jnp.where(act, nnz_pp[None, :], 0), axis=1,
                          dtype=jnp.int32)
        return algebra.step(carry, act, w_pp, deg_p), eq, rounds + 1

    eq0 = jnp.zeros(Qs, dtype=jnp.int32)
    carry, eq, _ = jax.lax.while_loop(cond, body, (carry0, eq0, jnp.int32(0)))

    # --- emissions: one contribution per (padded) out-slot ---
    payload = algebra.emit_payload(carry)
    emask = algebra.emit_mask(carry)
    cands = jax.vmap(lambda w: algebra.contrib(payload, w))(w_all[1:])
    dsts = dstp[p, 1:]                                    # [dmax]
    eq = eq + jnp.sum(jnp.where(emask[None], nnz_all[1:][:, None, :], 0),
                      axis=(0, 2), dtype=jnp.int32)

    # route to owner devices over the model axis: payload [ndev, dmax, Qs, B]
    owner = jnp.where(dsts >= 0, dsts // pl, -1)
    pay = jnp.full((ndev, dmax, Qs, B), algebra.identity, dtype=buf_row.dtype)
    slot_dst = jnp.full((ndev, dmax), -1, dtype=jnp.int32)

    def route(s, c):
        pay, slot_dst = c
        o = owner[s]
        valid = o >= 0
        oo = jnp.where(valid, o, 0)
        pay = pay.at[oo, s].set(jnp.where(valid, cands[s], pay[oo, s]))
        slot_dst = slot_dst.at[oo, s].set(
            jnp.where(valid, dsts[s] % pl, slot_dst[oo, s]))
        return pay, slot_dst

    pay, slot_dst = jax.lax.fori_loop(0, dmax, route, (pay, slot_dst))
    recv = jax.lax.all_to_all(pay, model_axis, 0, 0, tiled=False)
    recv_dst = jax.lax.all_to_all(slot_dst, model_axis, 0, 0, tiled=False)

    # --- write back own planes / yielded ops, apply received contributions --
    new_rows, keep_row = algebra.finish(carry, deg_p)
    buf = buf.at[p].set(keep_row)
    planes = tuple(x.at[p].set(nr) for x, nr in zip(planes, new_rows))
    flat_recv = recv.reshape(ndev * dmax, Qs, B)
    flat_dst = recv_dst.reshape(ndev * dmax)

    def apply_one(i, b):
        l = flat_dst[i]
        valid = l >= 0
        ll = jnp.where(valid, l, 0)
        new = algebra.combine(
            b[ll], jnp.where(valid, flat_recv[i], algebra.identity))
        return b.at[ll].set(jnp.where(valid, new, b[ll]))

    buf = jax.lax.fori_loop(0, ndev * dmax, apply_one, buf)
    return planes, buf, eq
