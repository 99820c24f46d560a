"""Figure 11 analogue: cumulative effect of individual techniques.

The paper stacks +buffer, +consolidation, +priority, +yielding onto a
Ligra baseline.  Here:

  baseline        global-frontier engine (Ligra t=1 analogue)
  +buffer         buffered partition execution, FIFO schedule, no yielding
                  (consolidation is structural in the dense buffer: the
                  min-write IS the paper's query-centric consolidation, so
                  it cannot be disabled — noted in DESIGN.md §2)
  +priority       priority-based partition scheduling
  +yield          Δ-window + edge-budget yielding (full ForkGraph)
"""
from __future__ import annotations

from benchmarks.common import rnd, sources_for, timed
from repro.core.baselines import global_minplus
from repro.core.queries import prepare, run_sssp
from repro.core.yielding import NO_YIELD, YieldConfig, default_delta
from repro.graphs.generators import build_suite


def run(quick: bool = True):
    rows = []
    graphs = ["road-ca"] if quick else ["road-ca", "road-us", "social-lj"]
    nq = 16 if quick else 64
    for gname in graphs:
        g = build_suite(gname)
        srcs = sources_for(g, nq, seed=6)
        bg, perm = prepare(g, 256)
        base, bsecs = timed(global_minplus, bg, perm[srcs])
        variants = [
            ("+buffer(fifo,noyield)",
             dict(schedule="fifo", yield_config=NO_YIELD)),
            ("+priority",
             dict(schedule="priority", yield_config=NO_YIELD)),
            ("+yield(full)",
             dict(schedule="priority",
                  yield_config=YieldConfig(
                      mu_factor=2.0, delta=default_delta(bg.weight_max)))),
        ]
        rows.append({"graph": gname, "variant": "baseline(global)",
                     "runtime_s": rnd(bsecs),
                     "edges_per_q": rnd(base.edges_processed.mean(), 0),
                     "speedup_vs_base": 1.0})
        for name, kw in variants:
            res, secs = timed(run_sssp, bg, perm[srcs], **kw)
            rows.append({
                "graph": gname, "variant": name, "runtime_s": rnd(secs),
                "edges_per_q": rnd(res.edges_processed.mean(), 0),
                "speedup_vs_base": rnd(bsecs / max(secs, 1e-9), 2)})
    return rows


COLUMNS = ["graph", "variant", "runtime_s", "edges_per_q",
           "speedup_vs_base"]
