"""Plan/geometry cache: content keying, LRU accounting, DevicePlan.stack,
and cache-on/cache-off bitwise equivalence through the serving tier."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.schedule import DevicePlan, PlanCache, cloud_content_key
from repro.core.workload import (BallBranch, GroupAllSpec, MSGLayerSpec,
                                 PointNetConfig, SALayerSpec)
from repro.data.pointcloud import request_stream
from repro.launch.serve import PointCloudServable, ServingEngine, ShapeBuckets
from repro.models import pointnet2 as pn
from repro.models.backend import compile_model


def tiny_config(n=64, c1=24, c2=8, k=4):
    return PointNetConfig(name="tiny-cache", n_points=n, layers=(
        SALayerSpec(n_centers=c1, n_neighbors=k, in_features=4,
                    mlp=(4, 8, 8, 16)),
        SALayerSpec(n_centers=c2, n_neighbors=k, in_features=16,
                    mlp=(16, 16, 16, 32)),
    ))


def tiny_msg(n=64):
    return PointNetConfig(name="tiny-cache-msg", n_points=n, layers=(
        MSGLayerSpec(n_centers=24, in_features=3, branches=(
            BallBranch(0.3, 4, (6, 8, 8, 16)),
            BallBranch(0.6, 8, (6, 8, 16)))),
        MSGLayerSpec(n_centers=8, in_features=32, branches=(
            BallBranch(0.5, 4, (35, 16, 32)),
            BallBranch(1.0, 8, (35, 16, 16)))),
    ), group_all=GroupAllSpec(in_features=48, mlp=(51, 32, 64)), head=(32,))


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_config()
    params = pn.init_params(jax.random.PRNGKey(0), cfg, n_classes=10)
    return cfg, params


def _cloud(n, seed=0):
    return np.random.default_rng(seed).normal(size=(n, 3)).astype(np.float32)


# ---------------------------------------------------------------------------
# content keys
# ---------------------------------------------------------------------------

def test_key_deterministic_and_content_sensitive():
    c = _cloud(64, seed=1)
    assert cloud_content_key(c) == cloud_content_key(c.copy())
    bumped = c.copy()
    bumped[3, 1] += 1e-6
    assert cloud_content_key(bumped) != cloud_content_key(c)


def test_key_is_row_order_sensitive():
    # FPS depends on row order, so a permuted cloud has a DIFFERENT plan:
    # permutations must NOT collide
    c = _cloud(64, seed=2)
    perm = c[np.random.default_rng(0).permutation(64)]
    assert cloud_content_key(perm) != cloud_content_key(c)


def test_key_trims_to_valid_rows():
    c = _cloud(48, seed=3)
    padded = np.zeros((64, 3), np.float32)
    padded[:48] = c
    assert cloud_content_key(padded, n_valid=48) == cloud_content_key(c)
    # the pad rows alone must not alias the full 64-row cloud
    assert cloud_content_key(padded) != cloud_content_key(c)


def test_key_shape_and_dtype_sensitive():
    c = _cloud(64, seed=4)
    assert (cloud_content_key(c.astype(np.float64))
            != cloud_content_key(c))
    assert (cloud_content_key(c.reshape(32, 6))
            != cloud_content_key(c.reshape(64, 3)))


# ---------------------------------------------------------------------------
# the LRU cache
# ---------------------------------------------------------------------------

def test_cache_hit_miss_accounting():
    cache = PlanCache(capacity=4)
    assert cache.get("a") is None
    cache.put("a", 1)
    assert cache.get("a") == 1
    assert cache.get_or_build("b", lambda: 2) == 2
    assert cache.get_or_build("b", lambda: 99) == 2     # no rebuild on hit
    s = cache.stats()
    # lookups: miss(a), hit(a), miss(b), hit(b) — put() itself is not a
    # lookup
    assert (s["hits"], s["misses"], s["size"]) == (2, 2, 2)
    assert s["hit_rate"] == pytest.approx(0.5)


def test_cache_evicts_coldest_at_capacity():
    cache = PlanCache(capacity=2)
    cache.put("a", 1); cache.put("b", 2)
    assert cache.get("a") == 1          # refresh 'a' -> 'b' is now coldest
    cache.put("c", 3)
    assert "b" not in cache and "a" in cache and "c" in cache
    assert len(cache) == 2
    assert cache.stats()["evictions"] == 1


def test_cache_clear_keeps_counters():
    cache = PlanCache(capacity=4)
    cache.put("a", 1); cache.get("a"); cache.get("zzz")
    cache.clear()
    assert len(cache) == 0
    s = cache.stats()
    assert s["hits"] == 1 and s["misses"] == 1


def test_cache_capacity_must_be_positive():
    with pytest.raises(ValueError):
        PlanCache(capacity=0)


# ---------------------------------------------------------------------------
# DevicePlan.stack
# ---------------------------------------------------------------------------

def test_device_plan_stack_batches_and_validates(setup):
    cfg, params = setup
    model = compile_model(params, cfg, schedule="pointer")
    p0 = model.build_device_plan(_cloud(64, seed=0))
    p1 = model.build_device_plan(_cloud(64, seed=1))
    stacked = DevicePlan.stack([p0, p1])
    assert stacked.order_of(1).shape == (2,) + p0.order_of(1).shape
    with pytest.raises(ValueError):
        DevicePlan.stack([])
    with pytest.raises(ValueError):
        DevicePlan.stack([p0, stacked])     # already batched


def test_build_device_plan_refuses_unplanned(setup):
    cfg, params = setup
    model = compile_model(params, cfg, schedule="baseline")
    assert not model.planned
    with pytest.raises(ValueError, match="unplanned"):
        model.build_device_plan(_cloud(64))


# ---------------------------------------------------------------------------
# through the serving tier
# ---------------------------------------------------------------------------

def test_engine_hits_on_repeated_stream(setup):
    cfg, params = setup
    model = compile_model(params, cfg, schedule="pointer")
    servable = PointCloudServable(
        model, buckets=ShapeBuckets(points=(64,), batch=(1, 2, 4)))
    engine = ServingEngine(servable)
    stream = list(request_stream(12, rate_hz=500.0, n_points=(64,),
                                 pool=3, repeat_p=0.8, seed=0))
    engine.serve_stream(stream)
    s = servable.plan_cache.stats()
    assert s["hits"] > 0
    assert s["misses"] <= 3 + 1        # at most the pool (+1 batch pad)
    assert s["hit_rate"] > 0


@pytest.mark.parametrize("device_planning", [True, False])
def test_cache_on_off_bitwise_equal(setup, device_planning):
    cfg, params = setup
    model = compile_model(params, cfg, backend="reram-fused",
                          schedule="pointer",
                          device_planning=device_planning)
    buckets = ShapeBuckets(points=(64,), batch=(1, 2, 4))
    clouds = [_cloud(64, seed=i) for i in range(3)]
    results = {}
    for cache in (True, False):
        engine = ServingEngine(PointCloudServable(
            model, buckets=buckets, plan_cache=cache))
        reqs = [engine.submit(c) for c in clouds]
        engine.drain()
        results[cache] = [jnp.asarray(r.result) for r in reqs]
    for a, b, c in zip(results[True], results[False], clouds):
        ref = model.forward(jnp.asarray(c))
        assert bool(jnp.all(a == ref)) and bool(jnp.all(b == ref))


def test_cache_rejected_for_uncacheable_models(setup):
    cfg, params = setup
    baseline = compile_model(params, cfg, schedule="baseline")
    # plan_cache=True silently degrades (nothing to cache) ...
    s = PointCloudServable(baseline)
    assert s.plan_cache is None
    # ... but an EXPLICIT cache on an uncacheable model is an error
    with pytest.raises(ValueError, match="no .*plan to cache"):
        PointCloudServable(baseline, plan_cache=PlanCache())


# ---------------------------------------------------------------------------
# the serving step stacks the batch's plans in its own program
# ---------------------------------------------------------------------------

#: batches served in this order through one bucket of four: each cloud's
#: point count and seed; seeds already served are plan-cache hits
_BATCHES = {
    "misses": ((64, 10), (40, 11), (56, 12), (64, 13)),
    "hits": ((64, 13), (64, 10), (56, 12), (40, 11)),
    "mixed": ((64, 10), (48, 14), (56, 12), (64, 15)),
    "pads": ((48, 16), (64, 13), (44, 17)),           # b_real 3 of 4
}


def _unit_cloud(n, seed):
    """A cloud inside the unit ball, so the MSG balls hold neighbours."""
    c = _cloud(n, seed)
    return c / np.linalg.norm(c, axis=1).max()


@pytest.fixture(scope="module", params=["ssg", "msg"])
def served_batches(request):
    """Serve :data:`_BATCHES` in order through one servable, counting
    :meth:`DevicePlan.stack` calls made on concrete arrays (outside any
    trace) and the step's traces after each batch. Returns the model, the
    per-batch results, the eager stack calls and the trace counts."""
    cfg = tiny_config() if request.param == "ssg" else tiny_msg()
    params = pn.init_params(jax.random.PRNGKey(2), cfg, n_classes=10)
    model = compile_model(params, cfg, backend="reram-fused",
                          schedule="pointer")
    servable = PointCloudServable(
        model, buckets=ShapeBuckets(points=(64,), batch=(4,)))
    engine = ServingEngine(servable)
    stack = DevicePlan.__dict__["stack"].__func__
    eager = []

    def counting_stack(cls, plans):
        plans = list(plans)
        if not isinstance(plans[0].orders[0], jax.core.Tracer):
            eager.append(len(plans))
        return stack(cls, plans)

    mp = pytest.MonkeyPatch()
    mp.setattr(DevicePlan, "stack", classmethod(counting_stack))
    served, traces, misses = {}, {}, {}
    try:
        for case, batch in _BATCHES.items():
            clouds = [_unit_cloud(n, seed) for n, seed in batch]
            m0 = servable.plan_cache.misses
            reqs = [engine.submit(c) for c in clouds]
            assert len(engine.step()) == len(clouds)   # one batch
            served[case] = (clouds, [np.asarray(r.result) for r in reqs])
            traces[case] = servable.jit_traces
            misses[case] = servable.plan_cache.misses - m0
    finally:
        mp.undo()
    return model, served, eager, traces, misses


@pytest.mark.parametrize("case", list(_BATCHES))
def test_served_logits_equal_the_stacked_plan_forward(served_batches, case):
    """Each served row equals ``batched_forward`` over the same padded
    batch with ``DevicePlan.stack`` of the rows' own plans (the pads
    reusing row 0's), bitwise."""
    model, served, _, _, misses = served_batches
    assert misses[case] == {"misses": 4, "hits": 0, "mixed": 2,
                            "pads": 2}[case]
    clouds, got = served[case]
    padded = np.zeros((4, 64, 3), np.float32)
    n_valid = np.empty((4,), np.int32)
    for i, c in enumerate(clouds):
        padded[i, :len(c)] = c
        n_valid[i] = len(c)
    padded[len(clouds):] = padded[0]
    n_valid[len(clouds):] = n_valid[0]
    build = jax.jit(lambda c, nv: model.build_device_plan(c, n_valid=nv))
    plans = [build(jnp.asarray(padded[i]), jnp.int32(n_valid[i]))
             for i in range(len(clouds))]
    plans += [plans[0]] * (4 - len(clouds))
    ref = np.asarray(model.batched_forward(
        jnp.asarray(padded), n_valid=jnp.asarray(n_valid),
        dplan=DevicePlan.stack(plans)))
    for i, row in enumerate(got):
        np.testing.assert_array_equal(row, ref[i])


def test_step_stacks_plans_in_trace_once_per_bucket(served_batches):
    """``run_batch`` makes no eager ``DevicePlan.stack`` call, and the step
    traces once for the bucket across miss, hit, mixed and padded
    batches."""
    _, _, eager, traces, _ = served_batches
    assert eager == []
    assert set(traces.values()) == {1}


def test_policy_plans_of_mixed_intra_trace_the_step_once():
    """A multi-candidate ``PlanPolicy`` plans on the host and picks
    ``intra`` per cloud; batches whose clouds chose different orderings
    (row 0's included) still trace the step once for the bucket, and each
    served row equals the model's own forward on that cloud."""
    from repro.core.policy import PlanPolicy
    cfg = tiny_config()
    params = pn.init_params(jax.random.PRNGKey(0), cfg, n_classes=10)
    # a 4-row window makes the candidates' elision counts differ
    model = compile_model(params, cfg, backend="reram-fused",
                          policy=PlanPolicy(window=4))
    assert not model.device_planning
    servable = PointCloudServable(
        model, buckets=ShapeBuckets(points=(64,), batch=(4,)))
    engine = ServingEngine(servable)
    chosen = set()
    for seeds in ((0, 3, 9, 1), (3, 9, 0, 4), (9, 5, 6), (7, 8, 2, 3)):
        clouds = [_cloud(64, s) for s in seeds]
        reqs = [engine.submit(c) for c in clouds]
        assert len(engine.step()) == len(clouds)          # one batch
        chosen |= {model.build_device_plan(c).intra for c in clouds}
        for c, r in zip(clouds, reqs):
            np.testing.assert_array_equal(np.asarray(r.result),
                                          np.asarray(model.forward(jnp.asarray(c))))
    assert chosen == {"index", "greedy", "morton"}
    assert servable.jit_traces == 1
