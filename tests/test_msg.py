"""PointNet++ multi-scale grouping (MSG): ball query, the grouped rows
``[p_j - p_i ; f_j]``, several branches per layer, group-all, and the
chunked index stream of the gather — each against a reference written
apart from the program (NumPy geometry and INT8 chain, the float32
``jax.numpy`` forward in ``repro.models.msg_reference``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compile_model
from repro.core.schedule import (complete_order, coordinate_layers,
                                 device_build_plan, greedy_nn_order)
from repro.core.workload import (POINTNET2_MSG, BallBranch, GroupAllSpec,
                                 MSGLayerSpec, PointNetConfig,
                                 PointNetWorkload, ball_query_np,
                                 select_mlp)
from repro.kernels import aggregate as ag
from repro.kernels import chain_quantizer
from repro.kernels.ref import quant_chain_np
from repro.launch.serve import PointCloudServable, ServingEngine, ShapeBuckets
from repro.models import pointnet2 as pn
from repro.models.msg_reference import msg_forward

#: the float program against the float32 reference, relative to the
#: largest |logit|: the same operations, but XLA may fuse and reorder the
#: float32 sums of the MLPs and the concatenations differently in the two
#: traces; ten float32 ulps of a logit of order 1 is 1.2e-6, and a
#: neighbour chosen differently at a ball's edge would move it by 1e-2
FLOAT_TOL = 1e-5


def tiny_msg(n=64):
    return PointNetConfig(name="tiny-msg", n_points=n, layers=(
        MSGLayerSpec(n_centers=24, in_features=3, branches=(
            BallBranch(0.3, 4, (6, 8, 8, 16)),
            BallBranch(0.6, 8, (6, 8, 16)))),
        MSGLayerSpec(n_centers=8, in_features=32, branches=(
            BallBranch(0.5, 4, (35, 16, 32)),
            BallBranch(1.0, 8, (35, 16, 16)))),
    ), group_all=GroupAllSpec(in_features=48, mlp=(51, 32, 64)), head=(32,))


def _cloud(n, seed):
    """A centred cloud inside the unit ball, like the benchmark's."""
    c = np.random.default_rng(seed).normal(size=(n, 3))
    return (c / np.linalg.norm(c, axis=1).max()).astype(np.float32)


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_msg()
    return cfg, pn.init_params(jax.random.PRNGKey(0), cfg, n_classes=10)


# ---------------------------------------------------------------------------
# specs and ball query
# ---------------------------------------------------------------------------

def test_published_msg_spec_widths():
    cfg = POINTNET2_MSG
    assert [l.out_features for l in cfg.layers] == [320, 640]
    assert [l.n_neighbors for l in cfg.layers] == [176, 224]
    assert cfg.out_features == 1024
    assert cfg.group_all.mlp == (643, 256, 512, 1024)
    with pytest.raises(ValueError, match="3 \\+ in_features"):
        MSGLayerSpec(n_centers=4, in_features=3,
                     branches=(BallBranch(0.1, 4, (3, 8)),))


@pytest.mark.parametrize("radius,k", [(0.15, 8), (0.4, 16), (2.5, 32)],
                         ids=["sparse-fill", "mixed", "all-hit"])
def test_ball_query_equals_numpy_oracle(radius, k):
    """Bitwise the NumPy oracle: first hits in index order, the slots
    past the last hit filled with the first hit (``sparse-fill`` leaves
    most balls short of k)."""
    pts = _cloud(96, seed=5)
    q = pts[::4]
    got = np.asarray(pn.ball_query(jnp.asarray(q), jnp.asarray(pts),
                                   radius, k))
    want = ball_query_np(q, pts, radius, k)
    np.testing.assert_array_equal(got, want)
    if radius < 0.2:      # really filled: some ball repeats its first hit
        assert (got[:, -1] == got[:, 0]).any()


def test_ball_query_masks_pad_rows():
    """Pad rows sitting on the queries are never selected once masked:
    the padded cloud's balls are the unpadded cloud's."""
    pts = _cloud(40, seed=6)
    q = pts[:10]
    padded = np.concatenate([pts, q + 1e-4])          # 10 pads, all hits
    want = ball_query_np(q, pts, 0.3, 12)
    got = np.asarray(pn.ball_query(jnp.asarray(q), jnp.asarray(padded),
                                   0.3, 12, n_valid=40))
    np.testing.assert_array_equal(got, want)
    unmasked = np.asarray(pn.ball_query(jnp.asarray(q), jnp.asarray(padded),
                                        0.3, 12))
    assert (unmasked >= 40).any()


def test_geometry_pass_equals_host_workload(setup):
    """Device FPS + ball queries (concatenated per layer) equal the host
    workload built in float32, padded or not."""
    cfg, _ = setup
    cloud = _cloud(56, seed=7)
    padded = np.zeros((64, 3), np.float32)
    padded[:56] = cloud
    wl = PointNetWorkload.build(
        cloud, PointNetConfig(cfg.name, 56, cfg.layers, cfg.group_all))
    _, ctr, nbr = pn.geometry_pass(cfg, jnp.asarray(padded), n_valid=56)
    for k in (1, 2):
        np.testing.assert_array_equal(np.asarray(ctr[k]), wl.centers[k])
        np.testing.assert_array_equal(np.asarray(nbr[k]), wl.neighbors[k])
        assert nbr[k].shape[1] == cfg.layers[k - 1].n_neighbors


# ---------------------------------------------------------------------------
# the forward against the references
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schedule", ["baseline", "pointer"])
def test_float_forward_matches_float32_reference(setup, schedule):
    cfg, params = setup
    model = compile_model(params, cfg, backend="float", schedule=schedule)
    for seed in range(3):
        cloud = jnp.asarray(_cloud(64, seed))
        ref = np.asarray(msg_forward(params, cfg, cloud))
        got = np.asarray(model.forward(cloud))
        assert np.abs(got - ref).max() <= FLOAT_TOL * np.abs(ref).max()


def _int8_oracle(params, cfg, cloud, program):
    """NumPy INT8 chain over host geometry: every branch, the group-all
    layer and the head through ``quant_chain_np``, one quantization scale
    per MLP input of one cloud. The quantizer is the program's own
    (``chain_quantizer``, as ``chip_smoke.py`` takes it): on some one-row
    heads the jitted quantizer and NumPy's land one ulp apart in the
    last dequantization, which is not what this test is about; the
    geometry, the grouped rows, the integer matmuls, the maxima and the
    concatenations stay the oracle's own."""
    q = lambda key: chain_quantizer(select_mlp(program, key))
    wl = PointNetWorkload.build(cloud, cfg)
    pts = cloud
    norm = np.sqrt(np.sum(pts * pts, axis=-1, keepdims=True))
    feats = pts / np.maximum(norm, np.float32(1e-6))
    for k, (spec, branches) in enumerate(zip(cfg.layers, params["sa"]), 1):
        ctr, nbr = wl.centers[k], wl.neighbors[k]
        outs, lo = [], 0
        for b, (br, mlp) in enumerate(zip(spec.branches, branches)):
            idx = nbr[:, lo:lo + br.n_neighbors]
            lo += br.n_neighbors
            rows = np.concatenate([pts[idx] - pts[ctr][:, None, :],
                                   feats[idx]], axis=-1)
            y, _ = quant_chain_np(mlp, rows.reshape(-1, rows.shape[-1]),
                                  quantize=q(("sa", k - 1, b)))
            outs.append(y.reshape(len(ctr), br.n_neighbors, -1).max(axis=1))
        pts, feats = pts[ctr], np.concatenate(outs, axis=-1)
    y, _ = quant_chain_np(params["sa_all"], np.concatenate([pts, feats], -1),
                          quantize=q("sa_all"))
    return quant_chain_np(params["head"], y.max(axis=0)[None],
                          final_relu=False, quantize=q("head"))[0][0]


def test_reram_fused_equals_numpy_int8_oracle(setup):
    """Exact: the per-cloud forward and the batched one, baseline and
    planned."""
    cfg, params = setup
    clouds = [_cloud(64, seed) for seed in (11, 12, 13)]
    oracle = None
    for schedule in ("baseline", "pointer"):
        model = compile_model(params, cfg, backend="reram-fused",
                              schedule=schedule)
        if oracle is None:
            oracle = np.stack([_int8_oracle(params, cfg, c,
                                            model.backend.program)
                               for c in clouds])
        per = np.stack([np.asarray(model.forward(jnp.asarray(c)))
                        for c in clouds])
        np.testing.assert_array_equal(per, oracle)
        np.testing.assert_array_equal(
            np.asarray(model.batched_forward(jnp.asarray(np.stack(clouds)))),
            oracle)


def test_served_logits_equal_per_cloud_forward(setup):
    """Through the normal serving path — plan cache, shape buckets of
    points and batch — every request's logits are bitwise its own
    forward's."""
    cfg, params = setup
    model = compile_model(params, cfg, backend="reram-fused",
                          schedule="pointer")
    assert model.device_planning
    servable = PointCloudServable(
        model, buckets=ShapeBuckets(points=(48, 64), batch=(1, 2, 4)))
    engine = ServingEngine(servable)
    clouds = [_cloud(n, seed=20 + i)
              for i, n in enumerate((64, 40, 64, 64, 48, 64, 56))]
    reqs = [engine.submit(c) for c in clouds]
    engine.drain()
    assert servable.batches >= 3
    for req, cloud in zip(reqs, clouds):
        ref = model.forward(jnp.asarray(cloud))
        assert bool(jnp.all(jnp.asarray(req.result) == ref)), req.id


def test_program_and_stats_per_branch(setup):
    cfg, params = setup
    model = compile_model(params, cfg, backend="reram-fused")
    prog = model.backend.program
    assert [len(p) for p in prog["sa"]] == [2, 2]
    assert prog["sa"][1][0].widths == (35, 16, 32)
    assert prog["sa_all"].widths == (51, 32, 64)
    plans = model.stats()["fused_plan"]
    assert list(plans) == ["sa0.b0", "sa0.b1", "sa1.b0", "sa1.b1",
                           "sa_all", "head"]
    # the predicted stream covers every branch's rows, with the balls
    # drawn in the device's float32 whatever the cloud's dtype
    cloud = _cloud(64, seed=14)
    dma = model.stats(cloud.astype(np.float64))["dma"]
    assert dma == model.stats(cloud)["dma"]
    assert dma["steps"] == sum(s.n_centers * s.n_neighbors
                               for s in cfg.layers)


def test_trace_contracts_count_branch_launches(setup):
    """One batch-gridded gather and one fused MLP launch per branch, plus
    the group-all MLP and the head, read off the jaxpr."""
    from repro import verify_contracts
    cfg, params = setup
    model = compile_model(params, cfg, backend="reram-fused",
                          schedule="pointer")
    clouds = jnp.asarray(np.stack([_cloud(64, s) for s in (41, 42, 43)]))
    report = verify_contracts(model, clouds).raise_if_violated()
    launches = report.info.launches_of("gather-batched")
    assert len(launches) == 4 and report.info.mlp_launches == 6
    assert all(rec.out_shape[0] == 3 for rec in launches)
    assert "sa1.b0@32" in report.vmem_rows


# ---------------------------------------------------------------------------
# the plan over concatenated receptive fields
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cfg,n,seed", [
    (tiny_msg(), 64, 31),
    (POINTNET2_MSG, 1024, 32),
], ids=["tiny", "published"])
def test_device_plan_equals_host_coordinate_oracle(cfg, n, seed):
    """The device plan over each centre's branches concatenated is the
    host Algorithm 1 walk over the same receptive fields, completed."""
    cloud = _cloud(n, seed)
    pts, _, nbr = pn.geometry_pass(cfg, jnp.asarray(cloud))
    plan = device_build_plan([nbr[k] for k in (1, 2)], pts[-1],
                             intra="greedy", coordinated=True)
    wl = PointNetWorkload.build(cloud, cfg)
    for k in (1, 2):
        np.testing.assert_array_equal(np.asarray(nbr[k]), wl.neighbors[k])
    host = coordinate_layers(wl, greedy_nn_order(wl.points[-1]))
    for k, size in ((1, cfg.layers[0].n_centers),
                    (2, cfg.layers[1].n_centers)):
        np.testing.assert_array_equal(
            np.asarray(plan.order_of(k)),
            complete_order(host.order_of(k), size, k))


# ---------------------------------------------------------------------------
# the chunked index stream
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,m,k,budget", [
    (3, 12, 7, 20),          # slices of one cloud's rows per launch
    (3, 12, 7, 50),
    (4, 8, 5, 100),          # whole clouds per launch, a short last one
    (2, 512, 200, ag.SMEM_INDEX_WORDS),   # past the real SMEM budget
], ids=["rows-20", "rows-50", "clouds-100", "past-smem"])
def test_chunked_gather_equals_unchunked(b, m, k, budget):
    rng = np.random.default_rng(b * m + k)
    n, c = 40, 6
    feats = jnp.asarray(rng.normal(size=(b, n, c)), jnp.float32)
    ctrs = jnp.asarray(rng.normal(size=(b, n, c)), jnp.float32)
    nbr = jnp.asarray(rng.integers(0, n, (b, m, k)), jnp.int32)
    ctr = jnp.asarray(rng.integers(0, n, (b, m)), jnp.int32)
    assert len(ag.index_chunks(b, m, k, budget)) > 1
    whole = ag._gather_diff(feats, nbr, ctr, ctrs, name="whole",
                            budget=b * m * (k + 1))
    chunked = ag._gather_diff(feats, nbr, ctr, ctrs, name="chunked",
                              budget=budget)
    np.testing.assert_array_equal(np.asarray(chunked), np.asarray(whole))
    want = (np.asarray(feats)[np.arange(b)[:, None, None], np.asarray(nbr)]
            - np.asarray(ctrs)[np.arange(b)[:, None], np.asarray(ctr)]
            [:, :, None])
    np.testing.assert_array_equal(np.asarray(whole), want)


def test_index_chunks_cover_rows_within_budget():
    for b, m, k, budget in [(16, 512, 128, ag.SMEM_INDEX_WORDS),
                            (16, 128, 128, ag.SMEM_INDEX_WORDS),
                            (16, 512, 16, ag.SMEM_INDEX_WORDS),
                            (2, 9, 4, 12)]:
        chunks = ag.index_chunks(b, m, k, budget)
        covered = [(bb, i) for b0, nb, m0, nm in chunks
                   for bb in range(b0, b0 + nb) for i in range(m0, m0 + nm)]
        assert covered == [(bb, i) for bb in range(b) for i in range(m)]
        assert all(nb * nm * (k + 1) <= max(budget, k + 1)
                   for _, nb, _, nm in chunks)
    # the published single-scale layers at batch 16 stay one launch
    assert len(ag.index_chunks(16, 512, 16)) == 1
