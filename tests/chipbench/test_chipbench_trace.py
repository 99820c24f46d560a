"""The trace reduction on hand-built event lists and on a recorded profile."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import trace  # noqa: E402
from chipbench.trace import Event, Trace  # noqa: E402


def _hand_trace():
    # device 0: two overlapping ops [10, 40) and [20, 50), an idle gap
    # [50, 70), one op [70, 90); the window is [0, 100)
    ops0 = [Event("fusion.1", 10, 40), Event("fusion.2", 20, 50),
            Event("fusion.1", 70, 90)]
    # device 1: busy [0, 60)
    ops1 = [Event("fusion.1", 0, 60)]
    mods = [[Event("jit_megastep(7)", 10, 50), Event("jit_other(3)", 70, 90)],
            [Event("jit_megastep(7)", 0, 60)]]
    host = [Event("harvest", 48, 68), Event("pump", 45, 52),
            Event("chipbench.batch", 0, 100)]
    return Trace(ops=[ops0, ops1], modules=mods, host=host, window=(0, 100))


def test_union_counts_overlap_once_and_clips_to_the_window():
    evs = [Event("a", 10, 40), Event("b", 20, 50), Event("c", 70, 90)]
    assert trace.union_ns(evs, 0, 100) == 60
    assert trace.union_ns(evs, 30, 80) == 30
    assert trace.union_ns([], 0, 100) == 0


def test_gaps_are_the_uncovered_stretches():
    evs = [Event("a", 10, 40), Event("b", 20, 50), Event("c", 70, 90)]
    assert trace.gaps(evs, 0, 100) == [(0, 10), (50, 70), (90, 100)]
    assert trace.gaps([Event("a", 0, 100)], 0, 100) == []


def test_busy_is_averaged_over_devices():
    t = _hand_trace()
    # device 0 busy 60 of 100, device 1 busy 60 of 100
    assert trace.busy_ns(t) == 60
    s = trace.summarize(t)
    assert s.window_s == pytest.approx(100e-9)
    assert trace.idle_share_pct(s) == pytest.approx(40.0)


def test_module_time_strips_the_program_id_and_averages():
    t = _hand_trace()
    assert trace.module_time_ns(t) == {"jit_megastep": 50.0,
                                       "jit_other": 10.0}


def test_idle_gaps_are_named_by_the_host_events_over_them():
    t = _hand_trace()
    found = trace.idle_gaps(t)
    # gaps of device 0: [0,10) 10 ns, [50,70) 20 ns, [90,100) 10 ns
    assert found[0] == ("chipbench.batch: harvest > pump",
                        pytest.approx(20e-9))
    assert {name for name, _ in found[1:]} == {"chipbench.batch: idle"}


def test_top_ops_sum_self_time_per_name():
    t = _hand_trace()
    ops = dict(trace.top_ops(t))
    # the overlap [20, 40) of device 0 counts once, for the later op:
    # fusion.1: (10 + 20 + 60) / 2 devices; fusion.2: 30 / 2
    assert ops["fusion.1"] == pytest.approx(45e-9)
    assert ops["fusion.2"] == pytest.approx(15e-9)


def test_a_loop_op_keeps_only_its_own_time():
    loop = Event("%while.3 = (f32[8]) while(...)", 0, 100)
    body = [Event("%fusion.1 = f32[8] fusion(...)", 10, 30),
            Event("%fusion.2 = f32[8] fusion(...)", 40, 90)]
    got = {e.name: s for e, s in trace.self_times([loop] + body)}
    assert got[loop.name] == 30 and got[body[1].name] == 50
    t = Trace(ops=[[loop] + body], modules=[[]], host=[], window=(0, 100))
    assert dict(trace.top_ops(t)) == pytest.approx(
        {"%while.3": 30e-9, "%fusion.1": 20e-9, "%fusion.2": 50e-9})


def test_a_cpu_profile_has_no_device_plane(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    t = trace.load(trace.latest_xplane(str(tmp_path)))
    assert t.ops == [] and t.window[1] > t.window[0]
    assert trace.summarize(t) is None
    assert trace.idle_share_pct(None) is None
