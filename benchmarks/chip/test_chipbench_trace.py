"""The benchmark's trace reduction on a small hand-written trace."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import trace as tr  # noqa: E402

#: one device, one harness thread. Times in ns (the proto holds ps).
#: window 1000..11000; ops: step kernels 2000-4000 and 5000-6000, a plan
#: program's op 7000-7500, and an op that straddles the window's end.
PROTO = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Modules"
    timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 1500000 duration_ps: 5000000 }
    events { metadata_id: 11 offset_ps: 6900000 duration_ps: 700000 }
  }
  lines {
    id: 2
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 7000000 duration_ps: 500000 }
    events { metadata_id: 4 offset_ps: 10500000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%reram_mlp_fused_wstat.3 = f32[16,2048,1024]{2,1,0} custom-call(s8[16,2048,1024]{2,1,0} %p)" } }
  event_metadata { key: 2 value { id: 2 name: "aggregate_diff_batched" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.12" } }
  event_metadata { key: 4 value { id: 4 name: "fusion.13" } }
  event_metadata { key: 10 value { id: 10 name: "jit__step(7)" } }
  event_metadata { key: 11 value { id: 11 name: "jit__lambda(3)" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 4500000 }
    events { metadata_id: 4 offset_ps: 6500000 duration_ps: 3000000 }
    events { metadata_id: 5 offset_ps: 6600000 duration_ps: 200000 }
  }
  lines {
    id: 2
    name: "other thread"
    timestamp_ns: 0
    events { metadata_id: 6 offset_ps: 0 duration_ps: 20000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.submit" } }
  event_metadata { key: 3 value { id: 3 name: "bench.block" } }
  event_metadata { key: 4 value { id: 4 name: "bench.step" } }
  event_metadata { key: 5 value { id: 5 name: "PjitFunction(_lambda)" } }
  event_metadata { key: 6 value { id: 6 name: "background" } }
}
"""


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData
    planes = tr.planes_from_profile(ProfileData.from_text_proto(PROTO))
    return tr.summarize(planes)


def test_window_busy_and_idle(summary):
    assert summary.window_ns == (1000.0, 11000.0)
    assert summary.window_s == pytest.approx(10e-6)
    # 2000 + 1000 + 500 + the 500 ns of the last op inside the window
    assert summary.busy_ns == pytest.approx(4000.0)
    assert sorted(summary.gaps) == [(1000.0, 2000.0), (4000.0, 5000.0),
                                    (6000.0, 7000.0), (7500.0, 10500.0)]
    assert summary.n_devices == 1


def test_kernel_and_program_time(summary):
    assert summary.op_seconds(r"^reram_mlp_fused") == pytest.approx(2e-6)
    assert summary.op_seconds(r"^aggregate_diff") == pytest.approx(1e-6)
    assert summary.op_seconds(r"^no_such_kernel") is None
    # ops take their program from the module event covering them
    mods = {tr.instruction(ev): mod for ev, mod, _ in summary.ops}
    assert mods["reram_mlp_fused_wstat.3"] == "jit__step"
    assert mods["fusion.12"] == "jit__lambda"
    assert mods["fusion.13"] == "?"
    outside = summary.module_seconds(lambda m: m != "jit__step")
    assert outside == pytest.approx(1.5e-6)


def test_breakdown_names_gaps_by_innermost_host_span(summary):
    bd = summary.breakdown()
    ops = dict((k, v) for k, v in bd["device_ops"])
    assert ops["jit__step:reram_mlp_fused_wstat"] == pytest.approx(2e-6)
    assert ops["jit__lambda:fusion"] == pytest.approx(0.5e-6)
    assert [k for k, _ in bd["device_ops"]][0] == \
        "jit__step:reram_mlp_fused_wstat"
    idle = dict((k, v) for k, v in bd["idle_gaps"])
    # 1000-2000 under bench.submit; 4000-5000 under bench.block;
    # 6000-7000 under bench.block (6000 < 6500); 7500-10500 under
    # bench.step (the PjitFunction span ended at 6800)
    assert idle == pytest.approx({"bench.submit": 1e-6,
                                  "bench.block": 2e-6,
                                  "bench.step": 3e-6})
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_innermost_span_sweep():
    ev = lambda n, s, d: tr.Event(n, float(s), float(d))
    s = tr.TraceSummary((0.0, 100.0), [], 0.0, [],
                        [ev("bench.window", 0, 100), ev("bench.step", 10, 50),
                         ev("PjitFunction(f)", 20, 5)], 1)
    assert s.host_spans_at([5, 12, 21, 30, 70]) == [
        "no host span", "bench.step", "PjitFunction(f)", "bench.step",
        "no host span"]


def test_union_clips_and_merges():
    covered, gaps = tr.union([(0, 5), (3, 8), (20, 30), (9, 9)], 2, 25)
    assert covered == 6 + 5
    assert gaps == [(8, 20)]
    assert tr.union([], 0, 10) == (0.0, [(0, 10)])


def test_dropped_events_are_refused():
    planes = [tr.Plane("/host:CPU", (tr.Line("python", (
                  tr.Event("bench.window", 0.0, 10.0),)),)),
              tr.Plane("/device:TPU:0", (
                  tr.Line("XLA Ops", (tr.Event("fusion.1", 1.0, 2.0),)),
                  tr.Line("XLA TraceMe", (tr.Event(tr.DROPPED, 3.0, 5.0),))))]
    with pytest.raises(RuntimeError, match="dropped"):
        tr.summarize(planes)


def test_trace_without_window_or_device_is_refused():
    host_only = [tr.Plane("/host:CPU", (tr.Line("python", (
        tr.Event("bench.window", 0.0, 10.0),)),))]
    with pytest.raises(RuntimeError, match="no device op"):
        tr.summarize(host_only)
    with pytest.raises(RuntimeError, match="bench.window"):
        tr.summarize([tr.Plane("/host:CPU", ())])


def test_op_name_strips_numeric_suffix():
    assert tr.op_name(tr.Event("fusion.12", 0, 1)) == "fusion"
    assert tr.op_name(tr.Event(
        "%while.16 = (s32[]{:T(128)}, s32[512]) while(%t), body=%b", 0, 1)) \
        == "while"
    assert tr.instruction(tr.Event(
        "%aggregate_diff_batched.2 = f32[16,512,16,1,16] custom-call()", 0,
        1)) == "aggregate_diff_batched.2"
    assert tr.op_name(tr.Event("reram_mlp_fused_whole", 0, 1)) == \
        "reram_mlp_fused_whole"
    assert tr.module_name("jit__step(12)") == "jit__step"


def test_nested_ops_count_once():
    ev = lambda n, s, d: tr.Event(n, float(s), float(d))
    ops = [(ev("while.3", 0, 100), "jit__lambda", "/device:TPU:0"),
           (ev("fusion.1", 10, 20), "jit__lambda", "/device:TPU:0"),
           (ev("fusion.2", 150, 50), "jit__lambda", "/device:TPU:0"),
           (ev("fusion.2", 150, 50), "jit__lambda", "/device:TPU:1"),
           (ev("aggregate_diff_batched.2", 300, 40), "jit__step",
            "/device:TPU:0")]
    s = tr.TraceSummary((0.0, 400.0), ops, 0.0, [], [], 1)
    # the while covers its body; the second chip's op counts apart
    assert s.module_seconds(lambda m: m == "jit__lambda") == \
        pytest.approx(200e-9)
    assert s.op_seconds(r"^aggregate_diff") == pytest.approx(40e-9)


def test_plan_time_is_the_builders_and_needs_the_step():
    """``plan.device_ms_per_cloud`` counts the plan builder's program
    alone, per cloud served, and reads nothing where the serving step's
    program is missing from the trace."""
    from types import SimpleNamespace

    from chipbench import loop, readers
    ev = lambda n, s, d: tr.Event(n, float(s), float(d))
    ops = [(ev("while.3", 0, 100), "jit__lambda", "/device:TPU:0"),
           (ev("concatenate.1", 120, 10), "jit_stack", "/device:TPU:0"),
           (ev("fusion.9", 200, 50), "jit__step", "/device:TPU:0")]
    summary = lambda o: tr.TraceSummary((0.0, 400.0), o, 0.0, [], [], 1)
    win = loop.Window("open", end=1.0, reqs=[loop.Req(0, 0.0, ready=0.5),
                                             loop.Req(1, 0.1, ready=0.5)])
    run = SimpleNamespace(trace=summary(ops), window=win)
    assert readers.plan_device_ms_per_cloud(run) == pytest.approx(
        100e-9 * 1e3 / 2)
    run.trace = summary(ops[:2])
    assert readers.plan_device_ms_per_cloud(run) is None
