"""The readers of the program's own spans, on a small hand-written
trace: the plan lookups' host time, dispatch-to-execution pairing, idle
time by overlap with the serving step, padding from span args."""
import os
import sys
from dataclasses import replace
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import spans  # noqa: E402
from chipbench import trace as tr  # noqa: E402

#: one device, one loop thread; times in ns (the proto holds ps).
#: window 0..10000. Two steps: the first serves one cloud padded to two
#: and builds its plan (the builder runs 1500-2600, the step 2700-4200);
#: the second serves two clouds from the plan cache (the plans' stacking
#: 6600-6650, the step 7600-8200). The device is idle 0-1500, 2600-2700,
#: 4200-6600, 6650-7600 and 8200-10000; the third gap opens in
#: bench.block and runs through bench.wait_arrival into the second step.
HOST = [  # name, start, duration, args
    ("bench.window", 0, 10000, ""),
    ("bench.step", 1000, 2000, ""),
    ("serve.step", 1000, 2000, 'stats { metadata_id: 1 str_value: "0" }'),
    ("serve.select", 1000, 100, ""),
    ("serve.run_batch", 1100, 1800,
     "stats { metadata_id: 2 int64_value: 1 } "
     "stats { metadata_id: 3 int64_value: 2 } "
     "stats { metadata_id: 4 int64_value: 1024 }"),
    ("serve.pad", 1100, 100, ""),
    ("serve.plan", 1200, 800, "stats { metadata_id: 5 int64_value: 0 }"),
    ("serve.plan_key", 1200, 100, ""),
    ("serve.plan_build", 1300, 600, ""),
    ("serve.plan_stack", 2000, 100, ""),
    ("serve.dispatch", 2100, 400, ""),
    ("serve.unpad", 2500, 400, ""),
    ("bench.block", 3000, 2000, ""),
    ("bench.wait_arrival", 5000, 1000, ""),
    ("bench.step", 6000, 2000, ""),
    ("serve.step", 6000, 2000, 'stats { metadata_id: 1 str_value: "1 2" }'),
    ("serve.run_batch", 6100, 1800,
     "stats { metadata_id: 2 int64_value: 2 } "
     "stats { metadata_id: 3 int64_value: 2 } "
     "stats { metadata_id: 4 int64_value: 1024 }"),
    ("serve.plan", 6200, 200, "stats { metadata_id: 5 int64_value: 1 }"),
    ("serve.plan", 6400, 100, "stats { metadata_id: 5 int64_value: 1 }"),
    ("serve.plan_stack", 6500, 100, ""),
    ("serve.dispatch", 7100, 400, ""),
    ("bench.block", 8000, 1500, ""),
]
HOST_STATS = ["requests", "batch_real", "batch_bucket", "points_bucket",
              "hit"]
#: device ops: name, start, duration
OPS = [
    ("while.3", 1500, 400), ("fusion.4", 1600, 100), ("fusion.5", 1900, 700),
    ("cond.2", 2350, 200),
    ("while.1", 2700, 300), ("aggregate_diff_batched.2", 3000, 500),
    ("reram_mlp_fused_whole.1", 3500, 700),
    ("concatenate.1", 6600, 50),
    ("fusion.8", 7600, 600),
]
MODULES = [("jit__lambda(3)", 1500, 1100), ("jit__step(7)", 2700, 1500),
           ("jit_concatenate(5)", 6600, 50), ("jit__step(7)", 7600, 600)]


def _proto() -> str:
    def events(rows, stats, key0=1):
        return "\n".join(
            f"events {{ metadata_id: {key0 + i} offset_ps: {s * 1000} "
            f"duration_ps: {d * 1000} {stats(row)} }}"
            for i, (row, s, d) in enumerate(rows))

    def names(rows, key0=1):
        return "\n".join(
            f"event_metadata {{ key: {key0 + i} value {{ id: {key0 + i} "
            f"name: '{row[0]}' }} }}" for i, row in enumerate(rows))

    def stat_names(names):
        return "\n".join(
            f'stat_metadata {{ key: {i + 1} value {{ id: {i + 1} '
            f'name: "{n}" }} }}' for i, n in enumerate(names))

    host = [(r, r[1], r[2]) for r in HOST]
    ops = [(r, r[1], r[2]) for r in OPS]
    mods = [(r, r[1], r[2]) for r in MODULES]
    return f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
{events(mods, lambda r: "", key0=100)}
  }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
{events(ops, lambda r: "")}
  }}
{names(OPS)}
{names(MODULES, key0=100)}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{events(host, lambda r: r[3])}
  }}
{names(HOST)}
{stat_names(HOST_STATS)}
}}
"""


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData
    planes = tr.planes_from_profile(ProfileData.from_text_proto(_proto()))
    return tr.summarize(planes)


def _run(trace):
    return SimpleNamespace(trace=trace)


def test_trace_holds_the_spans_and_their_args(summary):
    steps = spans.spans(summary, "serve.step")
    assert [str(ev.stats["requests"]) for ev in steps] == ["0", "1 2"]
    batches = spans.spans(summary, "serve.run_batch")
    assert [ev.stats["batch_real"] for ev in batches] == [1, 2]
    assert summary.gaps == [(0.0, 1500.0), (2600.0, 2700.0),
                            (4200.0, 6600.0), (6650.0, 7600.0),
                            (8200.0, 10000.0)]


def test_plan_host_time_per_step(summary):
    # 800 ns in the first step's one plan, 200 + 100 in the second's two
    assert spans.plan_host_ms_per_step(_run(summary)) == \
        pytest.approx(550e-6)
    assert spans.plan_host_ms_per_step(_run(None)) is None


def test_dispatch_pairs_with_the_steps_execution(summary):
    assert spans.executions(summary) == {"/device:TPU:0": [2700.0, 7600.0]}
    # 2100 -> 2700 and 7100 -> 7600
    assert spans.launch_ms(_run(summary)) == pytest.approx(550e-6)


def test_launch_is_none_on_a_count_mismatch_or_a_negative_delay(summary):
    first = [ev for ev in summary.host
             if not (ev.name == "serve.dispatch" and ev.start_ns > 7000)]
    assert spans.launch_ms(_run(replace(summary, host=first))) is None
    # with nothing run between them, the two steps read as one execution
    merged = replace(summary, ops=[op for op in summary.ops
                                   if op[1] != "jit_concatenate"])
    assert spans.executions(merged) == {"/device:TPU:0": [2700.0]}
    assert spans.launch_ms(_run(merged)) is None
    late = [tr.Event(ev.name, 7700.0, ev.dur_ns)
            if ev.name == "serve.dispatch" and ev.start_ns > 7000 else ev
            for ev in summary.host]
    assert spans.launch_ms(_run(replace(summary, host=late))) is None
    none = [ev for ev in summary.host if ev.name != "serve.dispatch"]
    assert spans.launch_ms(_run(replace(summary, host=none))) is None


def test_idle_in_serve_counts_by_overlap(summary):
    """The third gap opens in bench.block, so ``breakdown`` charges it
    all there (with the last gap); by overlap only its last 600 ns,
    inside the second serve.step, are the server's, with 500 ns of the
    first gap and the whole second and fourth."""
    idle = dict(summary.breakdown()["idle_gaps"])
    assert idle["bench.block"] == pytest.approx(2400e-9 + 1800e-9)
    assert spans.idle_in_serve_share(_run(summary)) == \
        pytest.approx(100.0 * (500 + 100 + 600 + 950) / 10000)
    no_steps = [ev for ev in summary.host if ev.name != "serve.step"]
    assert spans.idle_in_serve_share(
        _run(replace(summary, host=no_steps))) is None


def test_pad_share_from_span_args(summary):
    # rows 1 + 2 real of 2 + 2
    assert spans.pad_share(_run(summary)) == pytest.approx(25.0)
    bare = [tr.Event(ev.name, ev.start_ns, ev.dur_ns) for ev in summary.host]
    assert spans.pad_share(_run(replace(summary, host=bare))) is None
    assert spans.pad_share(_run(None)) is None
