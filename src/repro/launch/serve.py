"""End-to-end serving driver: continuous batching over batched requests.

Two workloads share the serving posture (DESIGN.md §4):

  lm     token serving — ContinuousBatcher over a reduced model twin
         (DESIGN.md §4.1)
  graph  graph-query serving — a multi-tenant GraphServer (DESIGN.md §4.2)
         multiplexes an arrival stream of mixed-kind requests onto
         per-(graph, kind) lane pools over the streaming megastep

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-72b --reduced \
        --requests 16 --batch 4 --max-new 12
    PYTHONPATH=src python -m repro.launch.serve --workload graph \
        --graph road-ca --kind mixed --requests 32 --batch 8 --tenants 2
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def serve_lm(args):
    import jax
    from repro.configs.base import get_config
    from repro.models.factory import build_model
    from repro.serve.engine import ContinuousBatcher, Request

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(args.seed)

    batcher = ContinuousBatcher(model, params, batch_size=args.batch,
                                max_len=args.max_len)
    for rid in range(args.requests):
        prompt = rng.integers(0, cfg.vocab,
                              rng.integers(4, 12)).astype(np.int32)
        extras = None
        if cfg.family == "vlm":
            extras = {"image_embeds": rng.normal(size=(
                cfg.num_image_tokens, cfg.d_model)).astype(np.float32)}
        if cfg.family == "encdec":
            extras = {"frames": 0.1 * rng.normal(size=(
                1500, cfg.d_model)).astype(np.float32)}
        batcher.submit(Request(rid=rid, prompt=prompt,
                               max_new_tokens=args.max_new,
                               extras=extras))
    t0 = time.perf_counter()
    out = batcher.run()
    dt = time.perf_counter() - t0
    print(f"[serve] {cfg.name}: {len(out)} requests, "
          f"{batcher.tokens_out} tokens in {batcher.steps} decode steps, "
          f"{dt:.2f}s ({batcher.tokens_out / dt:.1f} tok/s)")
    for rid in sorted(out)[:4]:
        print(f"  req {rid}: {out[rid]}")


def serve_graph(args):
    """Multi-tenant graph-query serving through the GraphServer pump."""
    from repro.graphs.generators import build_suite
    from repro.serve import GraphRequest, GraphServer

    g = build_suite(args.graph)
    rng = np.random.default_rng(args.seed)
    cand = np.flatnonzero(g.out_degree() > 0)
    sources = rng.choice(cand, size=args.requests, replace=True)
    kinds = (("sssp", "ppr") if args.kind == "mixed" else (args.kind,))
    # tenant 0 is the hot tenant (most of the offered load); equal weights,
    # so fair admission alone must keep the cold tenants served
    tenants = [f"tenant{i}" for i in range(args.tenants)]

    server = GraphServer(capacity=args.batch, k_visits=args.pump_visits,
                         seed=args.seed)
    server.register_graph(args.graph, g, num_queries=args.batch,
                          block_size=args.block_size)

    def arrivals():
        # one submission batch per serving round — the arrival process the
        # synchronous pump interleaves with chunk execution
        for lo in range(0, len(sources), args.batch):
            yield [GraphRequest(kind=kinds[i % len(kinds)], source=int(s),
                                graph=args.graph,
                                tenant=(tenants[0] if i % 4 else
                                        tenants[(i // 4) % len(tenants)]))
                   for i, s in enumerate(sources[lo: lo + args.batch],
                                         start=lo)]

    t0 = time.perf_counter()
    out = server.serve_forever(arrivals())
    dt = time.perf_counter() - t0
    ok = [r for r in out.values() if r.status == "ok"]
    if len(out) != len(sources):
        raise RuntimeError(
            f"server answered {len(out)} of {len(sources)} requests — "
            f"every submitted request must get a terminal response")
    lat = np.array([r.stats["latency_s"] for r in ok]) * 1e3
    print(f"[serve] graph={args.graph} |V|={g.n} kinds={'/'.join(kinds)} "
          f"tenants={args.tenants}: {len(ok)}/{len(out)} ok in "
          f"{server.rounds} rounds, {dt:.2f}s "
          f"({len(ok) / max(dt, 1e-9):.1f} q/s, capacity={args.batch}, "
          f"K={args.pump_visits})")
    if len(lat):
        print(f"  latency p50/p99: {np.percentile(lat, 50):.1f}/"
              f"{np.percentile(lat, 99):.1f} ms; per-request host syncs "
              f"p50: {np.percentile([r.stats['host_syncs'] for r in ok], 50):.0f}")


def main():
    from repro.graphs.generators import SUITES   # jax-free import
    from repro.launch.persistent_cache import enable_persistent_cache
    enable_persistent_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=("lm", "graph"), default="lm")
    # lm workload
    ap.add_argument("--arch", default="qwen2-72b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--max-new", type=int, default=12)
    # graph workload
    ap.add_argument("--graph", default="road-ca", choices=sorted(SUITES))
    ap.add_argument("--kind", choices=("sssp", "bfs", "ppr", "mixed"),
                    default="sssp")
    ap.add_argument("--block-size", type=int, default=256,
                    help="partition size; omit planner autotune on CPU demo")
    ap.add_argument("--pump-visits", type=int, default=8,
                    help="megastep chunk size K: visits per serving round")
    ap.add_argument("--tenants", type=int, default=2,
                    help="tenant count for the graph workload (tenant0 hot)")
    # shared
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.workload == "graph":
        serve_graph(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
