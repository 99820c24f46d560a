"""The program-span readers on hand-built spans and on a recorded profile."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import harness, spans  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402
from chipbench.spans import Span, Spans  # noqa: E402

READERS = ("init_state_share.batch", "rebuild_share.batch",
           "chunk_host_ms.batch", "reads_per_chunk.batch",
           "finalize_share.batch")


def _batch():
    # the window is [100, 1100) ns; one run of three chunks
    return Spans(window=(100, 1100), spans=[
        Span(spans.RUN, 50, 1200, {"chunks": 3, "chunk_reads": 14}),
        Span(spans.ENGINE, 50, 150, {}),               # 50 inside
        Span(spans.INIT_STATE, 150, 350, {}),
        Span(spans.INIT_STATE, 300, 400, {}),          # overlaps: once
        Span(spans.DISPATCH, 400, 500, {"traces": 1}),
        Span(spans.SYNC, 500, 600, {"visits": 64}),
        Span(spans.HARVEST, 600, 650, {}),
        Span(spans.DISPATCH, 700, 720, {"traces": 0}),
        Span(spans.HARVEST, 800, 830, {}),
        Span(spans.DISPATCH, 900, 1000, {"traces": 0}),
        Span(spans.HARVEST, 1080, 1120, {}),           # 20 inside
        Span(spans.FINALIZE, 1050, 1150, {}),          # 50 inside
    ])


@pytest.fixture
def hand(monkeypatch):
    monkeypatch.setattr(spans, "recorded", _batch)


def read(name):
    return harness.metric_reader(name).read(None)


def test_share_clips_to_the_window_and_unions_overlaps(hand):
    s = _batch()
    assert s.window_ns == 1000
    assert s.share_pct(s.named(spans.INIT_STATE)) == pytest.approx(25.0)
    assert s.share_pct([]) is None
    assert read("init_state_share.batch") == pytest.approx(25.0)
    assert read("finalize_share.batch") == pytest.approx(5.0)


def test_rebuild_counts_the_engine_and_the_dispatch_that_traced(hand):
    # engine 50 ns inside the window, the tracing dispatch 100 ns
    assert read("rebuild_share.batch") == pytest.approx(15.0)


def test_chunk_host_time_leaves_out_the_dispatch_that_traced(hand):
    # harvests 50 + 30 + 20 (clipped) ns and the untraced dispatches
    # 20 + 100 ns, over the 3 chunks, in ms
    assert read("chunk_host_ms.batch") == pytest.approx(220 / 3 / 1e6)


def test_reads_per_chunk_is_the_run_counters_ratio(hand):
    assert read("reads_per_chunk.batch") == pytest.approx(14 / 3)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_spans_reads_nothing(name, monkeypatch):
    monkeypatch.setattr(spans, "recorded",
                        lambda: Spans(window=(0, 100), spans=[]))
    assert read(name) is None
    monkeypatch.setattr(spans, "recorded", lambda: None)
    assert read(name) is None


def test_a_recorded_profile_gives_spans_with_their_args(tmp_path,
                                                        monkeypatch):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(spans.RUN, kind="sssp") as run:
        with jax.profiler.TraceAnnotation(spans.SYNC, chunk=0) as sp:
            jnp.ones((64, 64)).sum().block_until_ready()
            sp.set_metadata(visits=64)
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation(spans.HARVEST, chunk=0,
                                              reads=4):
                jnp.ones((64, 64)).sum().block_until_ready()
        run.set_metadata(chunks=1, chunk_reads=5)
    jax.profiler.stop_trace()
    monkeypatch.setattr(harness, "TRACE_DIR", str(tmp_path))
    s = spans.recorded()
    # the sync span ends before the window opens: only the spans that
    # overlap the window are kept
    assert sorted(x.name for x in s.spans) == [spans.HARVEST, spans.RUN]
    run_span, = s.named(spans.RUN)
    assert run_span.args == {"kind": "sssp", "chunks": 1, "chunk_reads": 5}
    assert s.named(spans.HARVEST)[0].args == {"chunk": 0, "reads": 4}
    assert read("reads_per_chunk.batch") == 5.0
    assert 0 < s.share_pct(s.named(spans.HARVEST)) <= 100
