"""The step's device time by part: op names to parts, the reduction over a
synthetic trace and over one recorded on a v5e, the clock lead, the
traced run of ``scope_profile.py`` on the CPU, and the unchanged reduction
of ``trace_small.xplane.pb``."""
from __future__ import annotations

import gzip
import os
import types

import harness_util
import pytest

from yardstick import scopes, spec, trace


@pytest.mark.parametrize("op_name,part", [
    ("jit(train_step)/jvp(embed_gather)/jit(_take)/gather", "gather"),
    ("jit(train_step)/transpose(jvp(embed_gather))/jit(_take)/scatter-add",
     "table_grad"),
    ("jit(train_step)/jvp(embed_quantize)/jit(round)/round", "quantize"),
    ("jit(train_step)/transpose(jvp(embed_quantize))/mul", "quantize"),
    ("jit(train_step)/jvp(tower)/dot_general", "tower"),
    ("jit(train_step)/transpose(jvp(tower))/mul;"
     "jit(train_step)/update/mul", "tower"),
    ("jit(train_step)/clip/sqrt", "clip"),
    ("jit(train_step)/update/jit(_where)/select_n", "update"),
    ("jit(train_step)/jvp()/add", "unscoped"),
    ("transpose(jvp(tower/embed_gather))/mul", "table_grad"),
    ("", "unscoped"),
])
def test_part_of_an_op_name(op_name, part):
    assert scopes.part_of(op_name) == part


HLO = '''HloModule jit_train_step, entry_computation_layout={()->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %gather.9 = f32[8]{0} gather(f32[8]{0} %param_0), metadata={op_name="jit(train_step)/jvp(tower)/gather"}
}

ENTRY %main.1 () -> f32[8] {
  %fusion.1 = f32[8]{0} fusion(), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(train_step)/jvp(embed_gather)/jit(_take)/gather" source_line=3}
  %scatter.2 = f32[8]{0} scatter(), metadata={op_name="jit(train_step)/transpose(jvp(embed_gather))/jit(_take)/scatter-add"}
  %copy.4 = f32[8]{0} copy(f32[8]{0} %scatter.2)
  ROOT %add_select_fusion = f32[8]{0} fusion(), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(train_step)/update/select_n"}
}
'''


def test_op_parts_of_hlo_text():
    got = scopes.op_parts(HLO)
    assert got["fusion.1"] == "gather"
    assert got["scatter.2"] == "table_grad"
    assert got["add_select_fusion"] == "update"
    assert got["copy.4"] == "unscoped"


# a device plane with two runs of the step program, [1, 5) and [7, 11) us,
# and a copy program between them; each step run holds a gather, a
# scatter, an op the compiled text lacks and an update, the gather and the
# scatter overlapping. Host plane: the window [0.5, 11.5) us and a
# trainer.dispatch span 0.25 us before each step run. Times in ps from 1 us.
SYNTH = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 11 offset_ps: 4500000 duration_ps: 1000000 }
    events { metadata_id: 10 offset_ps: 6000000 duration_ps: 4000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 20 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 21 offset_ps: 500000 duration_ps: 1500000 }
    events { metadata_id: 22 offset_ps: 2000000 duration_ps: 500000 }
    events { metadata_id: 23 offset_ps: 2500000 duration_ps: 1500000 }
    events { metadata_id: 24 offset_ps: 4500000 duration_ps: 1000000 }
    events { metadata_id: 20 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 21 offset_ps: 6500000 duration_ps: 1500000 }
    events { metadata_id: 22 offset_ps: 8000000 duration_ps: 500000 }
    events { metadata_id: 23 offset_ps: 8500000 duration_ps: 1500000 } }
  event_metadata { key: 10 value { id: 10 name: "jit_train_step(123)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_copy(456)" } }
  event_metadata { key: 20 value { id: 20 name: "%fusion.1 = f32[8]{0} fusion(), kind=kLoop" } }
  event_metadata { key: 21 value { id: 21 name: "%scatter.2 = f32[8]{0} scatter()" } }
  event_metadata { key: 22 value { id: 22 name: "%copy-start.7 = f32[8]{0} copy-start()" } }
  event_metadata { key: 23 value { id: 23 name: "%add_select_fusion = f32[8]{0} fusion()" } }
  event_metadata { key: 24 value { id: 24 name: "%copy.1 = f32[8]{0} copy()" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: -500000 duration_ps: 11000000 }
    events { metadata_id: 2 offset_ps: -250000 duration_ps: 100000 }
    events { metadata_id: 2 offset_ps: 5750000 duration_ps: 100000 } }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "trainer.dispatch" } }
}
'''


def _synth(text=SYNTH):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text)


def test_reduce_scopes_sums_the_step_runs_by_part():
    got = scopes.reduce_scopes(_synth(), HLO)
    assert got["module"] == "jit_train_step"
    assert got["runs"] == 2
    assert got["module_s"] == pytest.approx(8e-6)
    assert got["ops_s"] == pytest.approx(9e-6)
    assert got["mapped_s"] == pytest.approx(8e-6)
    assert got["parts"] == pytest.approx(
        {"gather": 2e-6, "table_grad": 3e-6, "quantize": 0.0, "tower": 0.0,
         "clip": 0.0, "update": 3e-6, "unscoped": 1e-6})
    assert sum(got["parts"].values()) == pytest.approx(got["ops_s"])


def test_reduce_scopes_of_a_named_program():
    got = scopes.reduce_scopes(_synth(), HLO, module="jit_copy")
    assert got["runs"] == 1
    assert got["parts"]["unscoped"] == pytest.approx(1e-6)
    assert got["mapped_s"] == 0


def test_reduce_scopes_counts_whole_runs_by_midpoint():
    # a window of [0.5, 6.5) us holds the first run's midpoint (3 us) and
    # not the second's (9 us)
    short = SYNTH.replace("duration_ps: 11000000", "duration_ps: 6000000")
    got = scopes.reduce_scopes(_synth(short), HLO, module="jit_train_step")
    assert got["runs"] == 1
    assert sum(got["parts"].values()) == pytest.approx(4.5e-6)


def test_reduce_scopes_without_a_window_is_an_error():
    with pytest.raises(ValueError, match="window"):
        scopes.reduce_scopes(_synth(SYNTH.replace('"window"', '"other"')),
                             HLO)


def test_clock_leads_pair_dispatches_with_runs_by_order():
    leads = scopes.clock_leads(_synth(), "trainer.dispatch",
                               "jit_train_step")
    assert leads == pytest.approx([0.25e-6, 0.25e-6])
    assert scopes.lead_summary(leads).startswith("2 runs, min 0.000 ms")
    assert scopes.clock_leads(_synth(), "trainer.dispatch", "jit_x") == []


def _recorded():
    from jax.profiler import ProfileData
    data = os.path.join(harness_util.TESTS, "data")
    with gzip.open(os.path.join(data, "trace_scoped.hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    return ProfileData.from_file(os.path.join(
        data, "trace_scoped.xplane.pb")), hlo


def test_recorded_scoped_v5e_trace():
    """Three steps of a tiny model through the program's ``Trainer``,
    recorded on a TPU v5 lite by ``record_scoped_trace.py``: every op of the
    step's runs is in the compiled text, the gather's forward and backward
    are apart, and the parts add up to the ops' time, which lies within the
    runs."""
    pd, hlo = _recorded()
    got = scopes.reduce_scopes(pd, hlo)
    assert got["module"] == "jit_train_step"
    assert got["runs"] == 3
    assert got["mapped_s"] == pytest.approx(got["ops_s"])
    parts = got["parts"]
    for part in ("gather", "table_grad", "tower", "clip", "update"):
        assert parts[part] > 0, part
    assert parts["quantize"] == 0
    assert sum(parts.values()) == pytest.approx(got["ops_s"])
    assert 0 < got["ops_s"] <= got["module_s"]
    assert len(scopes.clock_leads(pd, "trainer.dispatch", got["module"])) == 3


def test_recorded_trace_holds_the_trainer_spans():
    pd, _ = _recorded()
    names = [e.name for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events]
    for span in ("trainer.step", "trainer.data", "trainer.stage",
                 "trainer.dispatch"):
        assert names.count(span) == 3, span


def test_trace_small_reduction_is_unchanged():
    """Every key of the reduction of ``trace_small.xplane.pb``, as it
    reads where the step's parts were first read; the per-layer readers
    that use it must keep reading these numbers."""
    from jax.profiler import ProfileData
    path = os.path.join(harness_util.TESTS, "data", "trace_small.xplane.pb")
    got = trace.reduce_profile(ProfileData.from_file(path),
                               annotations=("sched_step", "wait"))
    assert got == {
        "busy_s": pytest.approx(4.9766e-05, rel=1e-12),
        "window_s": pytest.approx(0.064609184, rel=1e-12),
        "n_devices": 1,
        "device_ops": [
            ["jit__lambda:fusion", pytest.approx(2.6678e-05, rel=1e-12)],
            ["jit__lambda:convolution_tanh_fusion",
             pytest.approx(2.3054e-05, rel=1e-12)],
            ["jit__lambda:copy-start", pytest.approx(2.8e-08, rel=1e-12)],
            ["jit__lambda:copy-done", pytest.approx(6e-09, rel=1e-12)]],
        "idle_gaps": [["wait", pytest.approx(0.064559418, rel=1e-12)]],
        "modules": {"jit__lambda": [pytest.approx(4.9787e-05, rel=1e-12),
                                    2.0]}}


def test_scope_profile_takes_the_step_text_on_the_cpu():
    """``scope_profile.py``'s harness runs the cell whole at the reduced
    size: the step's compiled text, taken before the window, names every
    part; the CPU's trace holds no device plane, so nothing is read."""
    import jax

    import scope_profile
    from yardstick import peaks
    bench = spec.benchmark()
    cell = spec.workload(bench, "criteo-train-search")
    args = types.SimpleNamespace(seed=2**31 + 91, seconds=0.3, trace=1)
    h = scope_profile.ScopedHarness(
        bench, cell, args, jax.devices()[:1],
        cfg=harness_util.reduced_config(cell["config"]),
        mix=harness_util.reduced_mix(cell["traffic"]), log=lambda m: None,
        peaks=peaks.PEAKS["TPU v5 lite"])
    h.ref_mode = "f32"
    result = scope_profile.run.run_cell(h)
    assert result["correct"]
    assert h.scoped is None
    assert h.window_compiles is not None
    assert set(scopes.op_parts(h.hlo).values()) == set(scopes.PARTS)
