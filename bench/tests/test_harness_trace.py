"""The trace reduction: busy time, top operations and idle gaps named by
the host span over them."""
from __future__ import annotations

import os

import harness_util
import pytest

from yardstick import trace

# a device plane (two overlapping ops inside one module, nested ops on two
# lines) and a host plane (the window and two harness spans), times in ps
# from 1 us: window [1, 11) us, ops [2, 4) and [3, 5) us
SYNTH = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 5000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 11 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 12 offset_ps: 2000000 duration_ps: 2000000 } }
  event_metadata { key: 10 value { id: 10 name: "jit_step" } }
  event_metadata { key: 11 value { id: 11 name: "fusion.1" } }
  event_metadata { key: 12 value { id: 12 name: "copy.2" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 6000000 } }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "submit" } }
  event_metadata { key: 3 value { id: 3 name: "wait" } }
}
'''


def _synth():
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(SYNTH)


def test_busy_is_the_union_of_op_intervals():
    got = trace.reduce_profile(_synth(), annotations=("submit", "wait"))
    assert got["n_devices"] == 1
    assert got["window_s"] == pytest.approx(10e-6)
    assert got["busy_s"] == pytest.approx(3e-6)


def test_ops_are_named_by_their_module():
    got = trace.reduce_profile(_synth(), annotations=("submit", "wait"))
    assert dict(got["device_ops"]) == pytest.approx(
        {"jit_step:fusion.1": 2e-6, "jit_step:copy.2": 2e-6})


def test_programs_device_time_and_runs():
    got = trace.reduce_profile(_synth(), annotations=())
    assert got["modules"]["jit_step"] == pytest.approx([5e-6, 1])
    assert trace.main_program(got) == ("jit_step", pytest.approx(5e-6), 1)
    assert trace.main_program({"modules": {}}) is None


def test_idle_gaps_go_to_the_host_span_over_them():
    got = trace.reduce_profile(_synth(), annotations=("submit", "wait"))
    assert dict(got["idle_gaps"]) == pytest.approx({"submit": 1e-6,
                                                    "wait": 6e-6})
    unnamed = trace.reduce_profile(_synth(), annotations=())
    assert dict(unnamed["idle_gaps"]) == pytest.approx({"untraced": 7e-6})


def test_no_window_is_an_error():
    from jax.profiler import ProfileData
    pd = ProfileData.from_text_proto(SYNTH.replace('"window"', '"other"'))
    with pytest.raises(ValueError, match="window"):
        trace.reduce_profile(pd)


def test_recorded_v5e_trace():
    """A trace recorded on a TPU v5 lite by ``record_trace.py``: three calls
    of a jitted program, each inside ``sched_step`` and followed by a 20 ms
    sleep inside ``wait``."""
    path = os.path.join(harness_util.TESTS, "data", "trace_small.xplane.pb")
    from jax.profiler import ProfileData
    got = trace.reduce_profile(ProfileData.from_file(path),
                               annotations=("sched_step", "wait"))
    assert got["n_devices"] == 1
    assert 0.06 < got["window_s"] < 0.07
    assert 0 < got["busy_s"] < 1e-3
    names = [n for n, _ in got["device_ops"]]
    assert names[0].startswith("jit__lambda:")
    assert all(" = " not in n for n in names)
    gaps = dict(got["idle_gaps"])
    assert gaps["wait"] > 0.06 > sum(v for k, v in gaps.items()
                                     if k != "wait")
    assert sum(gaps.values()) + got["busy_s"] == pytest.approx(
        got["window_s"])
    name, seconds, runs = trace.main_program(got)
    # the first call's device events lie about 1 ms before the host's
    # window opens (the two clocks differ by that much), so it falls out
    assert name.startswith("jit__lambda") and runs == 2
    assert got["busy_s"] <= seconds < got["window_s"]
