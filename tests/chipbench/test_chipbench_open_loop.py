"""Open-loop traffic: a schedule fixed by the seed, lag charged to latency."""
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench.traffic import open_loop as ol  # noqa: E402

ZIPF = {"tenants": {"hot": 3, "cold": 1},
        "sources": {"dist": "zipf", "s": 1.1, "candidates": 64}}
UNIFORM = {"tenants": {"hot": 3, "cold": 1}, "sources": {"dist": "uniform"}}


def test_schedule_is_a_pure_function_of_seed_and_parameters():
    exclude = np.array([0, 1, 2])
    a = ol.schedule(ZIPF, 50.0, 4.0, 2**31 + 7, 1000, exclude)
    assert a == ol.schedule(ZIPF, 50.0, 4.0, 2**31 + 7, 1000, exclude)
    assert a != ol.schedule(ZIPF, 50.0, 4.0, 2**31 + 8, 1000, exclude)
    assert a != ol.schedule(UNIFORM, 50.0, 4.0, 2**31 + 7, 1000, exclude)
    assert all(0.0 <= x.at_s < 4.0 for x in a)
    assert [x.at_s for x in a] == sorted(x.at_s for x in a)


def test_schedule_rate_tenants_and_skew():
    exclude = np.arange(10)
    a = ol.schedule(ZIPF, 400.0, 10.0, 11, 5000, exclude)
    assert 3700 < len(a) < 4300
    hot = sum(x.tenant == "hot" for x in a) / len(a)
    assert 0.72 < hot < 0.78
    src = np.array([x.source for x in a])
    assert not np.isin(src, exclude).any()
    assert len(set(src.tolist())) <= 64
    # the top-ranked candidate takes far more than a uniform share
    assert np.bincount(src).max() > 5 * len(a) / 64
    u = ol.schedule(UNIFORM, 400.0, 10.0, 11, 5000, exclude)
    usrc = np.array([x.source for x in u])
    assert not np.isin(usrc, exclude).any()
    assert len(set(usrc.tolist())) > 2000


def test_driver_lag_is_charged_to_latency():
    arrivals = [ol.Arrival(0.0, "hot", 1), ol.Arrival(0.01, "hot", 2),
                ol.Arrival(0.02, "cold", 3)]

    def slow_submit(a):
        time.sleep(0.05)        # the driver falls behind its schedule
        return a.source

    t0 = time.perf_counter()
    sent = ol.drive(slow_submit, arrivals, t0)
    assert [s.rid for s in sent] == [1, 2, 3]
    assert sent[2].lag_s >= 0.05 * 3 - 0.02 - 0.01
    # each answer seen right after its submit: the latency still holds
    # the lag, since it runs from the scheduled arrival
    for s in sent:
        s.answered_s = s.arrival.at_s + s.lag_s
    lat = ol.latencies_ms(sent)
    assert np.allclose(lat, [s.lag_s * 1e3 for s in sent])
    assert lat[2] >= 100.0


def test_p95_counts_a_missing_answer_as_infinite():
    sent = [ol.Sent(ol.Arrival(0.0, "hot", i), i, 0.0, 0.01)
            for i in range(19)]
    sent.append(ol.Sent(ol.Arrival(0.0, "hot", 19), 19, 0.0, None))
    lat = ol.latencies_ms(sent)
    assert np.isinf(lat[-1])
    assert ol.p95(lat) == np.inf
    assert ol.p95(lat[:-1]) == lat[0]


def test_the_collector_stamps_answers_as_they_appear():
    answers = {}
    t0 = time.perf_counter()
    col = ol.Collector(answers.get, t0)
    col.start()
    col.expect(1)
    col.expect(2)
    time.sleep(0.02)
    answers[1] = "ok"
    t1 = time.perf_counter() - t0
    deadline = time.time() + 5
    while col.outstanding() > 1 and time.time() < deadline:
        time.sleep(0.001)
    col.closed.set()
    col.join(timeout=5)
    assert not col.is_alive()
    assert 1 in col.seen and 2 not in col.seen
    assert t1 <= col.seen[1] < t1 + 0.5
