#!/usr/bin/env python3
"""Chip smoke run: the FPPSession and GraphServer path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the distributed path on four chips

The graph is ``grid2d(512, 512, seed=0)``: 262,144 vertices and 1,046,528
directed edges, the scale of the DIMACS 9th Challenge road graph
USA-road-d.NY.  The session is planned for 64 queries at the block size
the planner picks for the attached chip.

One chip runs, through ``FPPSession.run``: sssp for 64 sources on the XLA
megastep and on the fused Pallas kernel (which must agree bit for bit),
checked against Dijkstra; bfs, checked exactly against the host BFS; and
push-mode ppr, checked against the sequential ACL push within 2·eps
(degree-normalised).  Then a ``GraphServer`` with one lane per pool
serves mixed sssp/ppr requests from two tenants, and a second one, at the
server's default capacity with autoscaling on, serves sssp requests from
two tenants over several live lanes; every answer must equal the bits of
``FPPSession.run`` for its source.

``--chips 4`` runs only the distributed runtime over all four devices and
what it is compared with: sssp bit-identical to the one-chip engine on
device 0, ppr within eps of it.

Every time and byte count printed is a smoke figure, not a benchmark
metric.  The last line of standard output is one JSON object; the exit
code is non-zero when no TPU is found or any check fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ROWS = 512
NUM_QUERIES = 64
ORACLE_SOURCES = 4        # sources checked against the host oracles
SERVED_SSSP = 8           # distinct sssp sources served (4 per tenant)
SERVED_PPR = 4            # ppr sources, each requested by both tenants
SERVED_POOLED = 16        # sssp sources served by the autoscaled server
ALPHA, EPS = 0.15, 1e-4
RESULT_TIMEOUT_S = 900.0
SEED = 0


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    log(f"[check] {'ok  ' if ok else 'FAIL'} {what}")
    if not ok:
        raise SmokeFailure(what)


def require_tpu(chips: int):
    """JAX's devices, if they are TPUs and there are enough of them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SmokeFailure(f"no TPU found: JAX's first device is "
                           f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise SmokeFailure(f"{chips} chips requested, {len(devices)} found")
    return devices


_COMPILE_S = [0.0]
_LISTENING = []


def _compile_seconds() -> float:
    """Seconds JAX has spent tracing, lowering and compiling so far."""
    if not _LISTENING:
        import jax

        def on_event(name, secs, **_):
            if name.startswith("/jax/core/compile/"):
                _COMPILE_S[0] += secs
        jax.monitoring.register_event_duration_secs_listener(on_event)
        _LISTENING.append(True)
    return _COMPILE_S[0]


def timed(label: str, fn):
    c0, t0 = _compile_seconds(), time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    comp = _compile_seconds() - c0
    log(f"[smoke] {label}: {wall:.1f} s wall; compile {comp:.1f} s "
        f"(summed over threads), run {max(wall - comp, 0.0):.1f} s")
    return out


def build(rows: int, num_queries: int, block_size=None):
    from repro.fpp import FPPSession
    from repro.graphs.generators import grid2d
    g = grid2d(rows, rows, seed=SEED)
    log(f"[smoke] graph grid2d({rows}, {rows}): {g.n:,} vertices, "
        f"{g.m:,} directed edges")
    sess = FPPSession(g).plan(num_queries=num_queries, block_size=block_size)
    bg, _ = timed("partition", sess.prepared)
    log(f"[smoke] plan: B={bg.block_size}, partitions={bg.num_parts}, "
        f"dmax={bg.nbr_part.shape[1]}, dense blocks={bg.blocks.shape[0]} "
        f"({bg.blocks.nbytes / 1e9:.2f} GB)")
    sources = np.random.default_rng(SEED).choice(g.n, num_queries,
                                                 replace=False)
    return g, sess, sources


def ppr_error(g, values: np.ndarray, ref: np.ndarray) -> float:
    """Max degree-normalised difference of two PPR mass vectors."""
    deg = np.maximum(g.out_degree(), 1)
    return float(np.max(np.abs(values - ref) / deg))


def check_sssp(g, sess, sources, n_oracle: int):
    from repro.core import oracles
    xla = timed("sssp, XLA megastep", lambda: sess.run(
        "sssp", sources, fused=False))
    fused = timed("sssp, fused kernel", lambda: sess.run(
        "sssp", sources, fused=True))
    check(np.array_equal(xla.values, fused.values)
          and np.array_equal(xla.edges_processed, fused.edges_processed),
          f"fused sssp bit-identical to the XLA megastep "
          f"({len(sources)} sources, values and edge counts)")
    ok = True
    for i in range(n_oracle):
        want, _ = oracles.dijkstra(g, int(sources[i]))
        ok &= bool(np.allclose(xla.values[i], want, rtol=1e-5, atol=0.0))
    check(ok, f"sssp matches Dijkstra at rtol 1e-5 on {n_oracle} sources")
    return xla


def check_bfs(g, sess, sources, n_oracle: int) -> None:
    from repro.core import oracles
    res = timed("bfs", lambda: sess.run("bfs", sources))
    ok = True
    for i in range(n_oracle):
        hops, _ = oracles.bfs(g, int(sources[i]))
        want = np.where(hops < 0, np.inf, hops).astype(np.float32)
        ok &= bool(np.array_equal(res.values[i], want))
    check(ok, f"bfs equals the host BFS exactly on {n_oracle} sources")


def check_ppr(g, sess, sources, n_oracle: int) -> None:
    from repro.core import oracles
    res = timed("ppr (push)", lambda: sess.run("ppr", sources, alpha=ALPHA,
                                               eps=EPS))
    err = max(ppr_error(g, res.values[i],
                        oracles.ppr_push(g, int(sources[i]), ALPHA, EPS)[0])
              for i in range(n_oracle))
    check(err <= 2 * EPS, f"ppr within 2*eps of the ACL push on {n_oracle} "
          f"sources (max degree-normalised error {err:.3g})")


def check_server(sess, sources, sssp_ref) -> None:
    """Two tenants race mixed sssp/ppr submissions against running lanes.

    One lane per pool (``capacity=1``, no autoscaling): a ppr lane's bits
    depend on which lanes share its visits, so each served ppr answer is
    held to a one-source ``sess.run``; sssp's exact min-plus fixpoint does
    not depend on the batch, so its answers are held to the 64-source
    run's rows.
    """
    from repro.serve import GraphRequest, GraphServer
    server = GraphServer(capacity=1, autoscaler=None, alpha=ALPHA, eps=EPS,
                         prewarm=("sssp", "ppr"))
    server.register_graph("road", sess)
    server.start()
    sssp_src = [int(s) for s in sources[:SERVED_SSSP]]
    ppr_src = [int(s) for s in sources[:SERVED_PPR]]
    plan = {"a": [("sssp", s) for s in sssp_src[0::2]]
            + [("ppr", s) for s in ppr_src],
            "b": [("ppr", s) for s in reversed(ppr_src)]
            + [("sssp", s) for s in sssp_src[1::2]]}
    booked = {t: [] for t in plan}

    def tenant(name):
        for kind, src in plan[name]:
            rid = server.submit(GraphRequest(kind=kind, source=src,
                                             graph="road", tenant=name))
            booked[name].append((rid, kind, src))

    def serve():
        threads = [threading.Thread(target=tenant, args=(t,)) for t in plan]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return [(server.result(rid, timeout=RESULT_TIMEOUT_S), kind, src)
                for reqs in booked.values() for rid, kind, src in reqs]

    try:
        answers = timed(f"GraphServer, {sum(map(len, plan.values()))} "
                        f"requests from 2 tenants", serve)
    finally:
        server.shutdown()
    check(all(r.status == "ok" for r, _, _ in answers),
          f"all {len(answers)} served requests ok")
    row = {int(s): i for i, s in enumerate(sources)}
    ppr_ref = {s: sess.run("ppr", np.array([s]), alpha=ALPHA, eps=EPS)
               for s in ppr_src}
    ok = True
    for r, kind, src in answers:
        if kind == "sssp":
            ok &= bool(np.array_equal(r.values, sssp_ref.values[row[src]]))
        else:
            ok &= bool(np.array_equal(r.values, ppr_ref[src].values[0])
                       and np.array_equal(r.residual,
                                          ppr_ref[src].residual[0]))
    check(ok, "every served answer equals the bits of sess.run for its "
          "source")
    stats = server.stats()
    log(f"[smoke] server: coalesced {stats['coalesced']}, result-cache hits "
        f"{stats['cache_hits']}, warm compiles "
        f"{stats['compile_cache']['misses']}")


def check_pooled_server(sess, sources, sssp_ref) -> None:
    """sssp at ``GraphServer``'s defaults: capacity 8, autoscaled
    power-of-two pools, the fused kernel chosen per kind, several lanes
    live in one pool.  The exact min-plus fixpoint does not depend on
    which lanes share a visit, so every answer is held to the bits of the
    64-source run."""
    from repro.serve import GraphRequest, GraphServer
    server = GraphServer(prewarm=("sssp",))
    server.register_graph("road", sess)
    server.start()
    src = [int(s) for s in sources[:SERVED_POOLED]]
    booked = {"a": [], "b": []}

    def tenant(name, part):
        for s in part:
            rid = server.submit(GraphRequest(kind="sssp", source=s,
                                             graph="road", tenant=name))
            booked[name].append((rid, s))

    def serve():
        threads = [threading.Thread(target=tenant, args=(t, src[i::2]))
                   for i, t in enumerate(booked)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return [(server.result(rid, timeout=RESULT_TIMEOUT_S), s)
                for reqs in booked.values() for rid, s in reqs]

    try:
        answers = timed(f"GraphServer (default capacity, autoscaled), "
                        f"{len(src)} sssp requests from 2 tenants", serve)
        capacity = server._pools[("road", "sssp")].capacity
    finally:
        server.shutdown()
    check(all(r.status == "ok" for r, _ in answers),
          f"all {len(answers)} autoscaled-server requests ok")
    row = {int(s): i for i, s in enumerate(sources)}
    check(all(np.array_equal(r.values, sssp_ref.values[row[s]])
              for r, s in answers),
          "every autoscaled-server answer equals the bits of sess.run")
    stats = server.stats()
    log(f"[smoke] autoscaled server: sssp pool capacity {capacity} at the "
        f"end, warm compiles {stats['compile_cache']['misses']}")


def memory_line(device) -> str:
    stats = device.memory_stats() or {}
    return (f"{device}: peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use', 'n/a')} bytes_in_use="
            f"{stats.get('bytes_in_use', 'n/a')} bytes_limit="
            f"{stats.get('bytes_limit', 'n/a')}")


def one_chip(rows: int = ROWS, num_queries: int = NUM_QUERIES,
             block_size=None) -> None:
    """Every one-chip phase; raises SmokeFailure on the first failed check."""
    g, sess, sources = build(rows, num_queries, block_size)
    n_oracle = min(ORACLE_SOURCES, num_queries)
    sssp_ref = check_sssp(g, sess, sources, n_oracle)
    check_bfs(g, sess, sources, n_oracle)
    check_ppr(g, sess, sources, n_oracle)
    check_server(sess, sources, sssp_ref)
    check_pooled_server(sess, sources, sssp_ref)


def four_chips(rows: int = ROWS, num_queries: int = NUM_QUERIES,
               block_size=None) -> None:
    """The distributed runtime over every device vs the one-chip engine."""
    import jax
    devices = jax.devices()
    g, sess, sources = build(rows, num_queries, block_size)
    mesh = jax.make_mesh((1, len(devices)), ("data", "model"))
    dist = timed(f"sssp, distributed over {len(devices)} devices",
                 lambda: sess.run("sssp", sources, backend="distributed",
                                  mesh=mesh))
    log(f"[smoke] supersteps: {dist.stats['supersteps']}")
    for d in devices:
        log(f"[smoke] after distributed sssp, {memory_line(d)}")
    eng = timed("sssp, engine on device 0", lambda: sess.run("sssp",
                                                             sources))
    check(np.array_equal(dist.values, eng.values),
          "distributed sssp bit-identical to the one-chip engine")
    dppr = timed("ppr, distributed", lambda: sess.run(
        "ppr", sources, backend="distributed", mesh=mesh, alpha=ALPHA,
        eps=EPS))
    eppr = timed("ppr, engine on device 0", lambda: sess.run(
        "ppr", sources, alpha=ALPHA, eps=EPS))
    err = max(ppr_error(g, dppr.values[i], eppr.values[i])
              for i in range(len(sources)))
    check(err <= EPS, f"distributed ppr within eps of the engine (max "
          f"degree-normalised difference {err:.3g})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed path over four chips")
    args = ap.parse_args(argv)
    from repro.launch.persistent_cache import enable_persistent_cache
    cache = enable_persistent_cache()
    try:
        devices = require_tpu(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    log(f"[smoke] devices: {len(devices)} x {devices[0].device_kind}; "
        f"compile cache {cache}")
    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(ROWS, NUM_QUERIES)
    except SmokeFailure as e:
        print(f"chip_smoke: check failed: {e}", file=sys.stderr)
        return 1
    for d in devices[:args.chips]:
        log(f"[smoke] {memory_line(d)}")
    log(f"[smoke] total {time.perf_counter() - t0:.1f} s wall, of which "
        f"compile {_compile_seconds():.1f} s (smoke figures, not metrics)")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
