"""What a profiler sees of the serving path: the named scopes of the plan
builder and the serving step in their compiled HLO (at model0's widths),
and the serving tier's ``serve.*`` host spans, their nesting and their
args, in a trace of served batches."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.workload import PAPER_MODELS, PointNetConfig, SALayerSpec
from repro.launch.serve import PointCloudServable, ServingEngine, ShapeBuckets
from repro.models import pointnet2 as pn
from repro.models.backend import compile_model

#: the stage scopes each program must carry, as paths below its top scope
BUILDER_SCOPES = ("plan/geometry/fps", "plan/geometry/knn", "plan/order",
                  "plan/coordinate")
STEP_SCOPES = ("step/geometry/fps", "step/geometry/knn", "step/gather",
               "step/mlp", "step/head")


def _scope_path(op_name: str) -> str:
    """An ``op_name`` with its transform and jit segments taken out:
    ``jit(_step)/step/vmap(geometry)/fps/while`` -> ``step/geometry/fps/
    while``."""
    segs = []
    for seg in op_name.split(";")[0].split("/"):
        seg = re.sub(r"^vmap\((.*)\)$", r"\1", seg)
        if seg and "(" not in seg:
            segs.append(seg)
    return "/".join(segs)


def _op_paths(hlo: str) -> set[str]:
    return {_scope_path(n) for n in re.findall(r'op_name="([^"]*)"', hlo)}


def _module_name(hlo: str) -> str:
    return re.search(r"^HloModule ([^,\s]+)", hlo, re.M).group(1)


@pytest.fixture(scope="module")
def model0_programs():
    """The compiled HLO text of model0's plan builder (one cloud) and its
    serving step (a batch of two, handed the list of the two plans it
    stacks, as ``run_batch`` hands it)."""
    cfg = PAPER_MODELS["model0"]
    params = pn.init_params(jax.random.PRNGKey(0), cfg, n_classes=40)
    model = compile_model(params, cfg, backend="reram-fused",
                          schedule="pointer")
    sv = PointCloudServable(model, buckets=ShapeBuckets(points=(1024,),
                                                        batch=(2,)))
    cloud = jax.ShapeDtypeStruct((cfg.n_points, 3), jnp.float32)
    nv = jax.ShapeDtypeStruct((), jnp.int32)
    builder = sv._jit_build.lower(cloud, nv).compile().as_text()
    plan = jax.eval_shape(sv._jit_build, cloud, nv)
    step = sv._jit_step.lower(
        jax.ShapeDtypeStruct((2, cfg.n_points, 3), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.int32), [plan, plan]
    ).compile().as_text()
    return builder, step


def test_programs_keep_their_names(model0_programs):
    """The step, lowered with the list of plans it stacks itself, is still
    the module ``jit__step``; the builder is ``jit__lambda``."""
    builder, step = model0_programs
    assert _module_name(builder) == "jit__lambda"
    assert _module_name(step) == "jit__step"


@pytest.mark.parametrize("which,scopes", [(0, BUILDER_SCOPES),
                                          (1, STEP_SCOPES)])
def test_op_names_carry_every_stage_scope(model0_programs, which, scopes):
    paths = _op_paths(model0_programs[which])
    for scope in scopes:
        assert any(p.startswith(scope + "/") for p in paths), scope
    top = scopes[0].split("/")[0]
    # nothing of the step sits under the builder's scope, or the reverse
    other = {"plan": "step", "step": "plan"}[top]
    assert not any(p.startswith(other + "/") for p in paths)


def test_builder_loops_lie_under_a_stage_scope(model0_programs):
    """Every ``while`` of the builder's entry computation (FPS and the
    greedy order; the coordination pass has none) is under one of the
    stage scopes, so the stages' device time covers the loops whole."""
    builder, _ = model0_programs
    entry = builder[builder.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    loops = [ln for ln in entry.splitlines() if " while(" in ln]
    assert loops
    stages = ("plan/geometry/", "plan/order/", "plan/coordinate/")
    for ln in loops:
        m = re.search(r'op_name="([^"]*)"', ln)
        assert m, ln[:120]
        assert _scope_path(m.group(1)).startswith(stages), m.group(1)


# -- host spans --------------------------------------------------------------

#: each span and the span it must sit in
PARENT = {"serve.select": "serve.step", "serve.run_batch": "serve.step",
          "serve.pad": "serve.run_batch", "serve.plan": "serve.run_batch",
          "serve.plan_key": "serve.plan", "serve.plan_build": "serve.plan",
          "serve.plan_stack": "serve.run_batch",
          "serve.dispatch": "serve.run_batch",
          "serve.unpad": "serve.run_batch"}


def _tiny_config():
    return PointNetConfig(name="tiny-spans", n_points=64, layers=(
        SALayerSpec(n_centers=16, n_neighbors=4, in_features=4,
                    mlp=(4, 8, 8, 16)),
        SALayerSpec(n_centers=8, n_neighbors=4, in_features=16,
                    mlp=(16, 16, 16, 32)),
    ))


def _serve_spans(tmp_path):
    """Serve two fresh clouds, then the same two again (plan-cache hits),
    under the profiler; returns the ``serve.*`` events of the thread that
    served them, in start order."""
    from jax.profiler import ProfileData
    cfg = _tiny_config()
    params = pn.init_params(jax.random.PRNGKey(1), cfg, n_classes=10)
    model = compile_model(params, cfg, backend="reram-fused",
                          schedule="pointer")
    engine = ServingEngine(PointCloudServable(
        model, buckets=ShapeBuckets(points=(64,), batch=(2, 4))))
    rng = np.random.default_rng(0)
    clouds = [rng.normal(size=(60, 3)).astype(np.float32) for _ in range(2)]
    for c in clouds:                                   # warm: compile
        engine.submit(c)
    jax.block_until_ready([r.result for r in engine.step()])
    engine.servable.plan_cache.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            for c in clouds:
                engine.submit(c)
            jax.block_until_ready([r.result for r in engine.step()])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats)) for e in line.events
                     if e.name.startswith("serve.")]
            if spans:
                return sorted(spans, key=lambda s: (s[1], -s[2]))
    raise AssertionError("no serve.* span in the trace")


def test_serve_spans_nest_and_carry_their_args(tmp_path):
    spans = _serve_spans(tmp_path)
    names = [s[0] for s in spans]
    assert set(names) == set(PARENT) | {"serve.step"}
    assert names.count("serve.step") == 2
    assert names.count("serve.plan") == 4
    assert names.count("serve.plan_build") == 2       # the misses only
    for i, (name, start, end, _) in enumerate(spans):
        if name == "serve.step":
            continue
        # the innermost span that contains it
        outer = [s for s in spans[:i] if s[1] <= start and end <= s[2]]
        assert outer, name
        assert min(outer, key=lambda s: s[2] - s[1])[0] == PARENT[name]
    steps = [s for s in spans if s[0] == "serve.step"]
    assert [str(s[3]["requests"]) for s in steps] == ["2 3", "4 5"]
    batches = [s[3] for s in spans if s[0] == "serve.run_batch"]
    assert batches == [{"batch_real": 2, "batch_bucket": 2,
                        "points_bucket": 64}] * 2
    hits = [s[3]["hit"] for s in spans if s[0] == "serve.plan"]
    assert hits == [0, 0, 1, 1]
    # the plans handed to the step: one per row of the batch bucket
    stacks = [s[3] for s in spans if s[0] == "serve.plan_stack"]
    assert stacks == [{"plans": b["batch_bucket"]} for b in batches]
