"""On-device planning: device_order_* / device_coordinate vs NumPy oracles.

The tentpole contract of on-device planning is bit-identity: on the same
coordinates (same dtype), each ``device_*`` function in
``repro.core.schedule`` must return exactly the permutation its NumPy
oracle returns — tie-breaks included. These property tests sweep ragged
sizes, clustered clouds (dense tie structure), explicit ``start`` indices,
and degenerate (planar/collinear) extents, comparing bitwise.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # deterministic sweep, see _hypothesis_fallback.py
    from _hypothesis_fallback import given, settings, st

from repro.core import (PAPER_MODELS, DevicePlan, PointNetConfig,
                        PointNetWorkload, SALayerSpec, build_plan)
from repro.core.schedule import (GREEDY_DENSE_LIMIT, complete_order,
                                 coordinate_layers, device_build_plan,
                                 device_coordinate, device_order_greedy,
                                 device_order_morton, greedy_nn_order,
                                 morton_order)


def tiny_config(n=64, c1=24, c2=8, k=4):
    return PointNetConfig(name="tiny", n_points=n, layers=(
        SALayerSpec(n_centers=c1, n_neighbors=k, in_features=4,
                    mlp=(4, 8, 8, 16)),
        SALayerSpec(n_centers=c2, n_neighbors=k, in_features=16,
                    mlp=(16, 16, 16, 32)),
    ))


def clustered(rng, n):
    """Tight clusters: many near-equal distances, so tie-breaks matter."""
    ctrs = rng.normal(size=(max(1, n // 8), 3)) * 4.0
    pick = rng.integers(0, ctrs.shape[0], size=n)
    return (ctrs[pick] + 0.25 * rng.normal(size=(n, 3))).astype(np.float32)


# ---------------------------------------------------------------------------
# intra-layer orders
# ---------------------------------------------------------------------------

@given(seed=st.integers(0, 10_000), n=st.integers(1, 96))
@settings(max_examples=25, deadline=None)
def test_device_greedy_matches_host_bitwise(seed, n):
    rng = np.random.default_rng(seed)
    for pts in (rng.normal(size=(n, 3)).astype(np.float32),
                clustered(rng, n)):
        start = seed % n
        host = greedy_nn_order(pts, start=start)
        dev = np.asarray(device_order_greedy(pts, start=start))
        assert np.array_equal(dev, host), (n, start)


@given(seed=st.integers(0, 10_000), n=st.integers(1, 96))
@settings(max_examples=25, deadline=None)
def test_device_morton_matches_host_bitwise(seed, n):
    rng = np.random.default_rng(seed)
    for pts in (rng.normal(size=(n, 3)).astype(np.float32),
                clustered(rng, n)):
        host = morton_order(pts)
        dev = np.asarray(device_order_morton(pts))
        assert np.array_equal(dev, host), n


def test_device_greedy_rejects_past_dense_limit():
    pts = np.zeros((GREEDY_DENSE_LIMIT + 1, 3), np.float32)
    with pytest.raises(ValueError, match="distance matrix"):
        device_order_greedy(pts)


# ---------------------------------------------------------------------------
# morton degenerate extents (the satellite fix)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("flat_axes", [(2,), (1, 2), (0, 1, 2)])
def test_morton_degenerate_extent_planar_collinear(flat_axes):
    """An axis with hi == lo (planar / collinear / single-point clouds)
    must quantize to bucket 0 — not through a fixed epsilon into garbage
    high bits — and host and device must agree bitwise."""
    rng = np.random.default_rng(7)
    pts = rng.normal(size=(48, 3)).astype(np.float32)
    for ax in flat_axes:
        pts[:, ax] = 1.5                       # exactly degenerate
    host = morton_order(pts)
    assert sorted(host.tolist()) == list(range(48))
    dev = np.asarray(device_order_morton(pts))
    assert np.array_equal(dev, host)
    if len(flat_axes) == 3:
        # every key identical -> stable sort keeps index order
        assert np.array_equal(host, np.arange(48))


def test_morton_degenerate_axis_ignores_live_axes_spread():
    """Regression: degenerate-axis handling must not perturb the buckets
    of the live axes. Collapsing z must give the same relative order as
    an explicitly 2-D-varying cloud with z pinned at any other value."""
    rng = np.random.default_rng(11)
    xy = rng.normal(size=(64, 2))
    a = np.column_stack([xy, np.full(64, 0.25)])
    b = np.column_stack([xy, np.full(64, -3.0)])
    assert np.array_equal(morton_order(a), morton_order(b))


def test_morton_subepsilon_spread_still_quantizes_by_true_extent():
    """A spread below the old 1e-12 epsilon is still a real extent: the
    two halves must land in different buckets (the old epsilon path
    collapsed them into one)."""
    pts = np.zeros((8, 3))
    pts[4:, 0] = 1e-13          # x spread far below the old epsilon
    order = morton_order(pts)
    # stable sort => low-x indices first, each half in index order
    assert np.array_equal(order, np.r_[np.arange(4), np.arange(4, 8)])
    key_lo = order[:4]
    assert set(key_lo) == {0, 1, 2, 3}


# ---------------------------------------------------------------------------
# coordination walk
# ---------------------------------------------------------------------------

def _host_coordinated_completed(wl, last_order):
    """The oracle in DevicePlan layout: Algorithm-1 walk, then orphan
    completion per layer (exactly what ExecutionPlan lowering runs)."""
    plan = coordinate_layers(wl, last_order)
    return [complete_order(np.asarray(plan.order_of(k)),
                           wl.points[k].shape[0], k)
            for k in range(1, wl.n_layers + 1)]


@given(seed=st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_device_coordinate_matches_host_walk(seed):
    wl = PointNetWorkload.random(tiny_config(), seed=seed)
    for intra in ("index", "greedy", "morton"):
        if intra == "index":
            last = np.arange(wl.points[-1].shape[0])
        elif intra == "greedy":
            last = greedy_nn_order(wl.points[-1])
        else:
            last = morton_order(wl.points[-1])
        host = _host_coordinated_completed(wl, last)
        nbrs = [wl.neighbors[k] for k in range(1, wl.n_layers + 1)]
        dev = device_coordinate(nbrs, last)
        for k, (h, d) in enumerate(zip(host, dev), start=1):
            assert np.array_equal(np.asarray(d), h), (intra, k)


@given(seed=st.integers(0, 10_000), c2=st.integers(2, 12))
@settings(max_examples=10, deadline=None)
def test_device_coordinate_orphan_completion_ragged(seed, c2):
    """Sparse coverage (c2*K < c1) guarantees orphans; the device walk must
    append exactly the host's ascending orphan tail."""
    wl = PointNetWorkload.random(tiny_config(n=128, c1=64, c2=c2, k=4),
                                 seed=seed)
    last = morton_order(wl.points[-1])
    host = _host_coordinated_completed(wl, last)
    dev = device_coordinate(
        [wl.neighbors[k] for k in range(1, wl.n_layers + 1)], last)
    for k, (h, d) in enumerate(zip(host, dev), start=1):
        assert np.array_equal(np.asarray(d), h), k


def three_layer_config(n=128, c1=64, c2=16, c3=4, k=4):
    return PointNetConfig(name="tiny3", n_points=n, layers=(
        SALayerSpec(n_centers=c1, n_neighbors=k, in_features=4,
                    mlp=(4, 8, 8, 16)),
        SALayerSpec(n_centers=c2, n_neighbors=k, in_features=16,
                    mlp=(16, 16, 16, 32)),
        SALayerSpec(n_centers=c3, n_neighbors=k, in_features=32,
                    mlp=(32, 32, 32, 64)),
    ))


def _device_coordinated(wl, last):
    return device_coordinate(
        [wl.neighbors[k] for k in range(1, wl.n_layers + 1)], last)


@pytest.mark.parametrize("c3,sparse", [(12, False), (2, True)])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_device_coordinate_three_layers_matches_host_walk(seed, c3, sparse):
    """Three layers: the walk recurses through layer 2, where a sparse top
    layer leaves orphan parents whose fields must not schedule layer 1."""
    wl = PointNetWorkload.random(three_layer_config(c3=c3), seed=seed)
    last = morton_order(wl.points[-1])
    walked_l2 = coordinate_layers(wl, last).order_of(2).shape[0]
    assert (walked_l2 < wl.points[2].shape[0]) == sparse
    host = _host_coordinated_completed(wl, last)
    for k, (h, d) in enumerate(zip(host, _device_coordinated(wl, last)),
                               start=1):
        assert np.array_equal(np.asarray(d), h), k


@pytest.fixture(scope="module")
def published_workload():
    """The served geometry: 1,024 points, 512/128 centres, K = 16."""
    return PointNetWorkload.random(PAPER_MODELS["model0"], seed=3)


@pytest.mark.parametrize("intra", ["index", "morton", "greedy"])
def test_device_coordinate_published_geometry(published_workload, intra):
    wl = published_workload
    assert [p.shape[0] for p in wl.points] == [1024, 512, 128]
    last = {"index": np.arange(wl.points[-1].shape[0]),
            "morton": morton_order(wl.points[-1]),
            "greedy": greedy_nn_order(wl.points[-1])}[intra]
    host = _host_coordinated_completed(wl, last)
    for k, (h, d) in enumerate(zip(host, _device_coordinated(wl, last)),
                               start=1):
        assert np.array_equal(np.asarray(d), h), (intra, k)


def test_device_coordinate_vmaps_per_cloud():
    """A vmapped walk over a batch of clouds equals the per-cloud walks."""
    import jax
    wls = [PointNetWorkload.random(three_layer_config(c3=c3), seed=s)
           for s, c3 in ((4, 2), (5, 2), (6, 2))]
    nbrs = [np.stack([w.neighbors[k] for w in wls]).astype(np.int32)
            for k in range(1, 4)]
    lasts = np.stack([morton_order(w.points[-1]) for w in wls]
                     ).astype(np.int32)
    batched = jax.vmap(device_coordinate)(nbrs, lasts)
    for b, wl in enumerate(wls):
        single = _device_coordinated(wl, lasts[b])
        for k, (bk, sk) in enumerate(zip(batched, single), start=1):
            assert np.array_equal(np.asarray(bk)[b], np.asarray(sk)), (b, k)


def test_device_coordinate_has_no_loop_or_branch():
    """The walk is a fixed number of vectorised ops: no scan, while or cond
    anywhere in its jaxpr."""
    import jax
    wl = PointNetWorkload.random(three_layer_config(), seed=0)
    nbrs = [wl.neighbors[k].astype(np.int32) for k in range(1, 4)]
    last = np.arange(wl.points[-1].shape[0], dtype=np.int32)
    jaxpr = jax.make_jaxpr(device_coordinate)(nbrs, last)

    def primitives(jx):
        for eqn in jx.eqns:
            yield eqn.primitive.name
            for v in eqn.params.values():   # nested jaxprs: pjit, branches
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from primitives(sub)

    found = set(primitives(jaxpr.jaxpr))
    assert not found & {"scan", "while", "cond"}, found


# ---------------------------------------------------------------------------
# end-to-end device_build_plan vs host build_plan lowering
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("intra,coordinated", [
    ("index", False), ("index", True), ("greedy", True),
    ("morton", True), ("morton", False),
])
def test_device_build_plan_matches_lowered_host_plan(intra, coordinated):
    """device_build_plan on float32 geometry == DevicePlan.lower of the
    host build_plan on the SAME float32 coordinates, order and inverse,
    every layer, bitwise."""
    cfg = tiny_config()
    wl64 = PointNetWorkload.random(cfg, seed=5)
    # host plan scored/built on the same dtype the device sees
    wl = PointNetWorkload(
        config=cfg,
        points=[p.astype(np.float32) for p in wl64.points],
        centers=wl64.centers, neighbors=wl64.neighbors)
    sizes = tuple(s.n_centers for s in cfg.layers)
    host_dp = DevicePlan.lower(
        build_plan(wl, intra=intra, coordinated=coordinated), sizes)
    nbrs = [wl.neighbors[k] for k in range(1, wl.n_layers + 1)]
    dev_dp = device_build_plan(nbrs, wl.points[-1], intra=intra,
                               coordinated=coordinated)
    assert dev_dp.layer_sizes == host_dp.layer_sizes
    for k in range(1, cfg.n_layers + 1):
        assert np.array_equal(np.asarray(dev_dp.order_of(k)),
                              np.asarray(host_dp.order_of(k))), k
        assert np.array_equal(np.asarray(dev_dp.inverse_of(k)),
                              np.asarray(host_dp.inverse_of(k))), k


def test_device_build_plan_traces_under_jit_and_vmap():
    """Plan construction itself is jit/vmap-traceable: same orders as the
    eager call, and a vmapped build yields a batched DevicePlan."""
    import jax
    import jax.numpy as jnp
    cfg = tiny_config()
    wls = [PointNetWorkload.random(cfg, seed=s) for s in (1, 2)]
    nbrs = [np.stack([w.neighbors[k] for w in wls]).astype(np.int32)
            for k in range(1, 3)]
    last = np.stack([w.points[-1] for w in wls]).astype(np.float32)

    def build(lp, nbs):
        return device_build_plan(nbs, lp, intra="morton", coordinated=True)

    dp = jax.vmap(build)(jnp.asarray(last), [jnp.asarray(n) for n in nbrs])
    assert dp.batched and dp.batch_size == 2
    jit_dp = jax.jit(build)(jnp.asarray(last[0]),
                            [jnp.asarray(n[0]) for n in nbrs])
    eager_dp = build(last[0], [n[0] for n in nbrs])
    for k in (1, 2):
        assert np.array_equal(np.asarray(dp.order_of(k))[0],
                              np.asarray(eager_dp.order_of(k))), k
        assert np.array_equal(np.asarray(jit_dp.order_of(k)),
                              np.asarray(eager_dp.order_of(k))), k
