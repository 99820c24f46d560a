"""Open-loop serving driver: requests arrive on a Poisson schedule fixed
by the seed, whatever the server does.

After ``benchmarks/bench_serve.py`` (``_schedule``, ``_drive``,
``_zipf_pick``): arrivals are a Poisson process at the configuration's
offered rate, conditioned on its mean count so that every seed offers
the same number of requests; a hot tenant sends ``hot`` requests for
every ``cold`` one; sources are Zipf-skewed over a seeded candidate set
or uniform over every vertex.  Every request is timed from its
*scheduled* arrival to the moment the client sees its answer, so a late
submit (driver lag, reported on its own line) counts against the server,
as an open loop must.

Cell parameters (``params`` of the cell file):

* ``kind``: the query kind served (``ppr``).
* ``sources``: ``{"dist": "zipf", "s": 1.1, "candidates": 4096}`` or
  ``{"dist": "uniform"}``.
* ``tenants``: ``{"hot": 3, "cold": 1}``: arrival shares by tenant.
* ``warm_requests``: requests served before the window, on sources kept
  out of the window's traffic.
* ``sample``: answers compared per path (``lane``, ``coalesced``,
  ``cached``), drawn from the seed.
* ``trace_seconds``: how much of the window's end a traced run records.

The configuration gives ``capacity``, ``offered_per_s`` and
``latency_limit_ms`` (with the sweep they came from), ``alpha``, ``eps``
and ``k_visits``.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional

import numpy as np

#: how often the client looks for answers; a latency is late by at most this
POLL_S = 0.001
#: how long after the window closes the client waits for the last answers
GRACE_S = 60.0


def zipf_pick(rng: np.random.Generator, candidates: np.ndarray, s: float,
              size: int) -> np.ndarray:
    """``size`` sources, Zipf(s)-skewed over the candidates' ranking."""
    ranks = np.arange(1, len(candidates) + 1, dtype=np.float64)
    p = ranks ** -float(s)
    return candidates[rng.choice(len(candidates), size=size, p=p / p.sum())]


@dataclasses.dataclass(frozen=True)
class Arrival:
    at_s: float         # scheduled offset from the start of the window
    tenant: str
    source: int


def schedule(params: dict, rate: float, seconds: float, seed: int, n: int,
             exclude: np.ndarray) -> List[Arrival]:
    """The window's arrivals: a pure function of its arguments.

    ``exclude`` (the warm-up sources) never appears, so no answer in the
    window comes from a cache the warm-up filled."""
    rng = np.random.default_rng([seed, 3])
    # a Poisson process given its count: every seed offers the same number
    # of requests, at other times
    count = int(round(rate * seconds))
    at = np.sort(rng.uniform(0.0, seconds, size=count))
    shares = params["tenants"]
    names = sorted(shares)
    weights = np.array([shares[t] for t in names], dtype=np.float64)
    tenant = rng.choice(len(names), size=count, p=weights / weights.sum())
    pool = np.setdiff1d(np.arange(n, dtype=np.int64), exclude)
    dist = params["sources"]
    if dist["dist"] == "zipf":
        cand = rng.choice(pool, size=min(int(dist["candidates"]), pool.size),
                          replace=False)
        src = zipf_pick(rng, cand, dist["s"], count)
    elif dist["dist"] == "uniform":
        src = pool[rng.integers(0, pool.size, size=count)]
    else:
        raise ValueError(f"unknown source distribution {dist['dist']!r}")
    return [Arrival(float(at[i]), names[int(tenant[i])], int(src[i]))
            for i in range(count)]


@dataclasses.dataclass
class Sent:
    arrival: Arrival
    rid: int
    lag_s: float                     # submit time minus scheduled time
    answered_s: Optional[float] = None   # offset of the answer, if it came


def drive(submit, arrivals: List[Arrival], t0: float) -> List[Sent]:
    """Submit each arrival at ``t0 + at_s`` (``time.perf_counter`` clock);
    returns what was sent with how late each submit was."""
    sent = []
    for a in arrivals:
        delay = t0 + a.at_s - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        rid = submit(a)
        sent.append(Sent(a, rid, time.perf_counter() - (t0 + a.at_s)))
    return sent


class Collector(threading.Thread):
    """Looks for answers every ``POLL_S`` and stamps when each was seen."""

    def __init__(self, poll, t0: float):
        super().__init__(name="chipbench-collector", daemon=True)
        self.poll, self.t0 = poll, t0
        self.lock = threading.Lock()
        self.waiting: Dict[int, None] = {}
        self.seen: Dict[int, float] = {}
        self.closed = threading.Event()

    def expect(self, rid: int):
        with self.lock:
            self.waiting[rid] = None

    def run(self):
        while not self.closed.is_set():
            with self.lock:
                rids = list(self.waiting)
            now = time.perf_counter() - self.t0
            for rid in rids:
                if self.poll(rid) is not None:
                    self.seen[rid] = now
                    with self.lock:
                        del self.waiting[rid]
            time.sleep(POLL_S)

    def outstanding(self) -> int:
        with self.lock:
            return len(self.waiting)


def latencies_ms(sent: List[Sent]) -> np.ndarray:
    """Scheduled arrival to answer, +inf for an answer that never came."""
    return np.array([np.inf if s.answered_s is None
                     else (s.answered_s - s.arrival.at_s) * 1e3
                     for s in sent])


def p95(values: np.ndarray) -> float:
    """The 95th percentile of all values, +inf included (no interpolation
    towards an infinite neighbour)."""
    return float(np.percentile(values, 95, method="higher"))


def warm_sources(params: dict, n: int, seed: int) -> np.ndarray:
    """The sources served before the window, kept out of its traffic."""
    return np.random.default_rng([seed, 4]).choice(
        n, size=int(params["warm_requests"]), replace=False)


def control_sources(cell, n: int, seed: int, seconds: float,
                    count: int) -> np.ndarray:
    """``count`` sources drawn from the window's own schedule for ``seed``,
    for the control to answer in the program's place."""
    arrivals = schedule(cell.params, float(cell.config["offered_per_s"]),
                        seconds, seed, n, warm_sources(cell.params, n, seed))
    src = np.array([a.source for a in arrivals])
    return np.random.default_rng([seed, 10]).choice(src, size=count)


# ------------------------------------------------------------ the driver


class System:
    """A running ``GraphServer`` for one configuration, warmed up."""

    def __init__(self, cell, data, seed: int):
        from repro.fpp import FPPSession
        from repro.serve import GraphRequest, GraphServer
        cfg, params = cell.config, cell.params
        self.kind = params["kind"]
        self.cfg, self.params, self.seed = cfg, params, seed
        self.edges = data.edges
        self.GraphRequest = GraphRequest
        self.session = FPPSession(data.program).plan(
            num_queries=int(cfg["capacity"]),
            block_size=cfg.get("block_size"))
        self.server = GraphServer(
            capacity=int(cfg["capacity"]), autoscaler=None,
            k_visits=int(cfg["k_visits"]), alpha=float(cfg["alpha"]),
            eps=float(cfg["eps"]), prewarm=(self.kind,))
        self.server.register_graph("road", self.session)
        self.server.start()
        self.warm = warm_sources(params, data.edges.n, seed)
        rids = [self.submit_source(int(s), "warm") for s in self.warm]
        for rid in rids:
            resp = self.server.result(rid, timeout=600.0)
            if resp.status != "ok":
                raise RuntimeError(f"warm-up request {rid}: {resp.status}")

    def submit_source(self, source: int, tenant: str) -> int:
        return self.server.submit(self.GraphRequest(
            kind=self.kind, source=source, graph="road", tenant=tenant))

    def close(self):
        if self.server is not None:
            self.server.shutdown()
        self.server = self.session = None


def prepare(cell, data, seed: int) -> System:
    return System(cell, data, seed)


def measure(system: System, seconds: float, tracer) -> dict:
    """The measured window; returns the run's end-to-end values, counters
    and the answers to compare."""
    cfg, params = system.cfg, system.params
    arrivals = schedule(params, float(cfg["offered_per_s"]), seconds,
                        system.seed, system.edges.n, system.warm)
    server = system.server
    before = server.stats()
    t0 = time.perf_counter() + 0.05
    collector = Collector(server.poll, t0)
    collector.start()

    def submit(a: Arrival) -> int:
        rid = system.submit_source(a.source, a.tenant)
        collector.expect(rid)
        return rid

    # a traced run records the window's last ``trace_seconds``
    trace_s = min(float(params.get("trace_seconds", seconds)), seconds)
    starter = threading.Timer(
        max(0.0, t0 + seconds - trace_s - time.perf_counter()), tracer.start)
    stopper = threading.Timer(max(0.0, t0 + seconds - time.perf_counter()),
                              tracer.stop)
    starter.start()
    stopper.start()
    try:
        with tracer.span("chipbench.drive"):
            sent = drive(submit, arrivals, t0)
        close_s = time.perf_counter() - t0
        while (collector.outstanding()
               and time.perf_counter() - t0 < max(close_s, seconds) + GRACE_S):
            time.sleep(0.01)
    finally:
        starter.join()
        stopper.join()
        collector.closed.set()
        collector.join()
    after = server.stats()
    for s in sent:
        s.answered_s = collector.seen.get(s.rid)
    responses = {s.rid: server.poll(s.rid) for s in sent}
    ok = [s for s in sent if responses[s.rid] is not None
          and responses[s.rid].status == "ok"]
    lat = latencies_ms(sent)
    ok_rids = {s.rid for s in ok}
    ok_mask = np.array([s.rid in ok_rids for s in sent], dtype=bool)
    lat_ok = np.where(ok_mask, lat, np.inf)
    limit = float(cfg["latency_limit_ms"])
    lags = np.array([s.lag_s for s in sent]) * 1e3
    return {
        "attempted": len(sent),
        "failed": len(sent) - len(ok),
        "end_to_end": {
            "served_p95_ms": p95(lat_ok) if sent else float("inf"),
            "served_goodput_per_s": float(np.count_nonzero(
                lat_ok <= limit)) / seconds,
        },
        "notes": [
            f"open loop: {len(sent)} requests at {cfg['offered_per_s']}/s "
            f"offered over {seconds} s; driver lag p50 "
            f"{np.median(lags) if sent else 0:.3f} ms, p95 "
            f"{np.percentile(lags, 95) if sent else 0:.3f} ms, max "
            f"{lags.max() if sent else 0:.3f} ms; last answer "
            f"{max([s.answered_s or 0 for s in sent], default=0):.3f} s "
            f"after the window opened",
        ],
        "counters": _counters(sent, responses, before, after),
        "answers": _answers(system, sent, responses),
    }


def _counters(sent, responses, before, after) -> dict:
    lanes = [responses[s.rid] for s in sent if responses[s.rid] is not None
             and responses[s.rid].status == "ok"
             and not responses[s.rid].stats.get("cached")
             and not responses[s.rid].stats.get("coalesced")]
    waits = [r.stats["queue_wait_s"] for s in sent
             if (r := responses[s.rid]) is not None]
    return {
        "requests": len(sent),
        "cache_hits": after["cache_hits"] - before["cache_hits"],
        "coalesced": after["coalesced"] - before["coalesced"],
        "queue_wait_s": waits,
        "lane_host_syncs": [r.stats["host_syncs"] for r in lanes],
        "lane_visits": [r.stats["visits"] for r in lanes],
    }


def _path(resp) -> str:
    if resp.stats.get("cached"):
        return "cached"
    if resp.stats.get("coalesced"):
        return "coalesced"
    return "lane"


def _answers(system: System, sent, responses):
    """The sample to compare: per path up to ``sample[path]`` answers drawn
    from the seed, the lane answer with the most visits always among them,
    and every request that never got an ok answer."""
    from chipbench.compare import Answer
    rng = np.random.default_rng([system.seed, 5])
    by_path: Dict[str, list] = {"lane": [], "coalesced": [], "cached": []}
    out = []
    for s in sent:
        r = responses[s.rid]
        if r is None or r.status != "ok":
            out.append(Answer(system.kind, s.arrival.source, None, "missing"))
        else:
            by_path[_path(r)].append(r)
    for path, rs in by_path.items():
        want = int(system.params["sample"].get(path, 0))
        if not rs or not want:
            continue
        idx = set(rng.choice(len(rs), size=min(want, len(rs)),
                             replace=False).tolist())
        if path == "lane":
            idx.add(int(np.argmax([r.stats["visits"] for r in rs])))
        out.extend(Answer(system.kind, int(rs[i].source), rs[i].values, path,
                          residual=rs[i].residual) for i in sorted(idx))
    return out


def release(system: System):
    system.close()
