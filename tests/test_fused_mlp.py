"""Fused multi-layer MLP kernel vs per-layer path vs float reference.

Numerics contract (see ``fused_mlp.py``): the integer crossbar pipeline is
exact; float dequant agrees with the separately-compiled per-layer path to
~1 ulp (XLA FMA contraction). So exactness is asserted bitwise in
*scale-controlled* regimes where every float op is IEEE-exact (quant scales
are exact integers, all values exactly representable), and random-float
equivalence is asserted at ulp-level tolerance far below one quant LSB.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:      # deterministic sweep, see _hypothesis_fallback.py
    from _hypothesis_fallback import given, settings, st

from repro.kernels import (FUSED_MODES, CrossbarProgram, build_program,
                           chain_quantizer, plan_fused_mlp, quantize_tensor,
                           requantize, reram_linear, reram_mlp_fused,
                           reram_mlp_fused_batched)
from repro.kernels.program import VMEM_BUDGET_BYTES, fused_vmem_bytes
from repro.kernels.ref import combine_planes, quant_chain_np, quantize_np

RNG = np.random.default_rng(0)


def _mk_layers(widths, rng, zero_bias=False):
    return [{"w": jnp.asarray(rng.normal(size=(k, n)), jnp.float32),
             "b": jnp.zeros((n,), jnp.float32) if zero_bias else
             jnp.asarray(rng.normal(size=(n,)), jnp.float32)}
            for k, n in zip(widths[:-1], widths[1:])]


def _sequential(layers, x, final_relu=True):
    """The per-layer path: ``reram_linear`` chain exactly as ``_apply_mlp``
    runs it with ``matmul=reram_linear``."""
    y = x
    for i, lyr in enumerate(layers):
        y = reram_linear(y, lyr["w"]) + lyr["b"]
        if final_relu or i < len(layers) - 1:
            y = jax.nn.relu(y)
    return y


def _float_ref(layers, x, final_relu=True):
    y = np.asarray(x, np.float64)
    for i, lyr in enumerate(layers):
        y = y @ np.asarray(lyr["w"], np.float64) + np.asarray(
            lyr["b"], np.float64)
        if final_relu or i < len(layers) - 1:
            y = np.maximum(y, 0)
    return y


# ---------------------------------------------------------------------------
# exactness: scale-controlled regimes (every float op IEEE-exact)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(9, 5, 7), (37, 19, 23), (128, 128, 128),
                                   (130, 70, 140)])
def test_single_layer_integer_exact_vs_oracle(m, k, n):
    """With max|x| = max|w| = 127 both quant scales are exactly 1.0, so the
    fused float output must EQUAL the pure integer matmul oracle bitwise —
    this proves the in-kernel plane shift-and-add + offset correction +
    dequant pipeline is integer-exact."""
    xi = RNG.integers(-127, 128, (m, k))
    wi = RNG.integers(-127, 128, (k, n))
    xi[0, 0] = 127
    wi[0, 0] = 127
    prog = build_program([{"w": jnp.asarray(wi, jnp.float32),
                           "b": jnp.zeros((n,), jnp.float32)}])
    out = reram_mlp_fused(jnp.asarray(xi, jnp.float32), prog,
                          final_relu=False)
    ref = (xi @ wi).astype(np.float32)
    assert bool(jnp.all(out == ref))


def test_three_layer_integer_exact_chain():
    """Multi-layer exactness: layers 1-2 are 127*I and a 127*permutation, so
    every requantization scale is an exact integer (127, then 127^2) and the
    intermediate requant must reproduce the inputs exactly; layer 3 is a
    random int crossbar. All float ops stay exact -> bitwise equality with
    the pure-integer oracle across the whole fused 3-stage pipeline."""
    k, n = 8, 12
    x = RNG.integers(0, 128, (50, k))
    x[0, 0] = 127                              # pins every scale
    perm = np.eye(k)[RNG.permutation(k)]
    w3 = RNG.integers(-127, 128, (k, n))
    w3[0, 0] = 127
    layers = [
        {"w": jnp.asarray(127.0 * np.eye(k), jnp.float32),
         "b": jnp.zeros((k,), jnp.float32)},
        {"w": jnp.asarray(127.0 * perm, jnp.float32),
         "b": jnp.zeros((k,), jnp.float32)},
        {"w": jnp.asarray(w3, jnp.float32),
         "b": jnp.zeros((n,), jnp.float32)},
    ]
    prog = build_program(layers)
    out = reram_mlp_fused(jnp.asarray(x, jnp.float32), prog,
                          final_relu=False)
    ref = ((x @ perm.astype(np.int64)) @ w3).astype(np.float32) \
        * np.float32(16129.0)                  # 127^2, the exact scale chain
    assert bool(jnp.all(out == ref))


@pytest.mark.parametrize("widths,m,final_relu", [
    ((5, 7), 9, True),
    ((3, 64, 10), 33, True),
    ((4, 64, 64, 128), 516, True),
    ((4, 64, 64, 128), 1, False),
    ((130, 200, 70), 257, True),
])
def test_zero_bias_bitwise_vs_quantized_oracle(widths, m, final_relu):
    """With zero biases every float op in the fused kernel is a single
    correctly-rounded IEEE operation (no FMA-contraction site), so the
    kernel must match the NumPy quantized-chain oracle BITWISE on random
    floats — requantization scales, int pipeline and dequant all exact.
    (The XLA-compiled per-layer path itself deviates from this oracle by
    ~1 ulp, which is why the sequential comparison below uses tolerance.)"""
    layers = _mk_layers(widths, np.random.default_rng(1), zero_bias=True)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(m, widths[0])),
                    jnp.float32)
    fused = reram_mlp_fused(x, build_program(layers), final_relu=final_relu)
    oracle, _ = quant_chain_np(layers, x, final_relu=final_relu)
    assert np.array_equal(np.asarray(fused), oracle)


@pytest.mark.parametrize("m,n", [(9, 7), (130, 64), (516, 200)])
def test_requantize_matches_quantize_tensor_bitwise(m, n):
    """The kernel's inter-layer requantizer, run alone, is
    ``quantize_tensor`` and its NumPy twin bitwise where f32 division is
    correctly rounded (here)."""
    y = jnp.asarray(np.maximum(np.random.default_rng(3).normal(size=(m, n)),
                               0), jnp.float32)
    q, s = requantize(y)
    q_ref, s_ref = quantize_tensor(y)
    q_np, s_np = quantize_np(y)
    assert q.dtype == jnp.int32 and np.array_equal(q, q_ref)
    assert np.array_equal(np.asarray(q), q_np)
    assert np.float32(s) == np.float32(s_ref) == s_np


@pytest.mark.parametrize("widths,m,final_relu", [
    ((4, 64, 64, 128), 516, True),
    ((130, 200, 70), 257, False),
])
def test_chain_quantizer_oracle_bitwise(widths, m, final_relu):
    """The oracle fed the program's own integers and the device's own
    quantizer (``chain_quantizer``) gives the fused kernel's output and
    the default oracle's accumulators, bitwise."""
    layers = _mk_layers(widths, np.random.default_rng(4), zero_bias=True)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(m, widths[0])),
                    jnp.float32)
    prog = build_program(layers)
    fused = reram_mlp_fused(x, prog, final_relu=final_relu)
    y, accs = quant_chain_np(layers, x, final_relu=final_relu,
                             quantize=chain_quantizer(prog))
    _, accs_np = quant_chain_np(layers, x, final_relu=final_relu)
    assert np.array_equal(np.asarray(fused), y)
    assert all(np.array_equal(a, b) for a, b in zip(accs, accs_np))


# ---------------------------------------------------------------------------
# equivalence: random floats, non-128 shapes, 1/2/3-layer MLPs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("widths,m", [
    ((5, 7), 9),                 # 1 layer, tiny
    ((17, 100, 2), 200),         # 2 layers, none 128-aligned
    ((4, 64, 64, 128), 516),     # 3 layers, the paper's SA-1 shape
    ((130, 200, 70), 257),       # 3-D of dims straddle a 128 boundary
])
def test_fused_matches_sequential_and_float(widths, m):
    rng = np.random.default_rng(7)
    layers = _mk_layers(widths, rng)
    x = jnp.asarray(rng.normal(size=(m, widths[0])), jnp.float32)
    fused = np.asarray(reram_mlp_fused(x, build_program(layers)))
    seq = np.asarray(_sequential(layers, x))
    # ulp-level agreement with the per-layer path (same ints, same scales)
    np.testing.assert_allclose(fused, seq, rtol=1e-5,
                               atol=1e-5 * max(1.0, np.abs(seq).max()))
    # quantization-tolerance agreement with the float reference
    ref = _float_ref(layers, x)
    tol = 0.05 * np.abs(ref).max() + 0.1
    assert np.max(np.abs(fused - ref)) <= tol


def test_leading_dims_like_sa_layer():
    """(M, K, C) activations — the sa_layer aggregation layout — flatten
    through the fused kernel exactly like a (M*K, C) matrix."""
    rng = np.random.default_rng(3)
    layers = _mk_layers((8, 32, 16), rng)
    prog = build_program(layers)
    x = jnp.asarray(rng.normal(size=(13, 16, 8)), jnp.float32)
    out3 = reram_mlp_fused(x, prog)
    out2 = reram_mlp_fused(x.reshape(-1, 8), prog)
    assert out3.shape == (13, 16, 16)
    assert bool(jnp.all(out3 == out2.reshape(13, 16, 16)))


# ---------------------------------------------------------------------------
# N/K tiling: tiled vs whole-layer bitwise, VMEM budget, ragged widths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("widths,m,zero_bias", [
    ((130, 200, 70), 257, False),    # every real width ends mid-tile
    ((4, 64, 64, 128), 300, False),  # d_pad == tile edge (single N-tile)
    ((17, 300, 140), 65, True),
])
def test_tiled_matches_whole_layer_bitwise(widths, m, zero_bias):
    """The N/K tiling must be invisible: int32 accumulation is associative
    and every float op runs elementwise on identical values, so tiled and
    whole-layer outputs are bitwise equal — including with biases, and
    including real widths not divisible by the tile edge (the per-tile
    col_mask regression)."""
    rng = np.random.default_rng(21)
    layers = _mk_layers(widths, rng, zero_bias=zero_bias)
    prog = build_program(layers)
    x = jnp.asarray(rng.normal(size=(m, widths[0])), jnp.float32)
    whole = reram_mlp_fused(x, prog, block_n=prog.d_pad)
    tiled = reram_mlp_fused(x, prog, block_n=128, block_k=128)
    assert bool(jnp.all(whole == tiled))
    seq = np.asarray(_sequential(layers, x))
    np.testing.assert_allclose(np.asarray(tiled), seq, rtol=1e-5,
                               atol=1e-5 * max(1.0, np.abs(seq).max()))


def test_model2_layer2_d1024_tiled_within_budget():
    """The acceptance geometry: model2's layer-2 MLP (512, 512, 512, 1024)
    at its real row count (128 centers x 16 neighbors = 2048). The
    whole-layer dataflow busts the 16 MB VMEM budget, the auto-selector
    picks an N-tiled plan that fits, and the tiled kernel matches the
    sequential ``reram_linear`` chain BITWISE on the zero-bias integer
    pipeline."""
    widths, m = (512, 512, 512, 1024), 2048
    rng = np.random.default_rng(22)
    layers = _mk_layers(widths, rng, zero_bias=True)
    prog = build_program(layers)
    assert prog.d_pad == 1024

    plan = plan_fused_mlp(prog, m)
    assert plan.whole_bytes > VMEM_BUDGET_BYTES      # whole layer: too big
    assert plan.tiled and plan.d_pad % plan.block_n == 0
    assert plan.vmem_bytes <= VMEM_BUDGET_BYTES      # per-layer-tile: fits
    assert plan.fits_budget

    x = jnp.asarray(rng.normal(size=(m, widths[0])), jnp.float32)
    fused = reram_mlp_fused(x, prog, final_relu=False)   # auto plan = tiled
    seq = _sequential(layers, x, final_relu=False)
    assert np.array_equal(np.asarray(fused), np.asarray(seq))


# ---------------------------------------------------------------------------
# M-tiled + j-outer dataflows: every mode is bitwise the same pipeline
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["tiled", "mtiled", "wstat"])
@pytest.mark.parametrize("widths,m,zero_bias", [
    ((130, 200, 70), 257, False),    # every real width ends mid-tile
    ((4, 64, 64, 128), 300, False),  # d_pad == tile edge (single N-tile)
    ((17, 300, 140), 65, True),
])
def test_modes_match_whole_layer_bitwise(widths, m, zero_bias, mode):
    """The equivalence sweep: the M/N/K tiling, the HBM activation panel
    ('mtiled': f32 stripes round-trip through HBM exactly), and the j-outer
    loop order ('wstat': int accumulation associative, max order-free) must
    all be invisible — bitwise-equal outputs vs the whole-layer dataflow on
    shapes where every mode fits, including biases and ragged real
    widths."""
    rng = np.random.default_rng(21)
    layers = _mk_layers(widths, rng, zero_bias=zero_bias)
    prog = build_program(layers)
    x = jnp.asarray(rng.normal(size=(m, widths[0])), jnp.float32)
    whole = reram_mlp_fused(x, prog, mode="whole")
    out = reram_mlp_fused(x, prog, mode=mode,
                          block_n=min(128, prog.d_pad), block_k=128)
    assert bool(jnp.all(whole == out))
    # and ~1 ulp vs the separately-compiled per-layer path
    seq = np.asarray(_sequential(layers, x))
    np.testing.assert_allclose(np.asarray(out), seq, rtol=1e-5,
                               atol=1e-5 * max(1.0, np.abs(seq).max()))


@pytest.mark.parametrize("mode", ["mtiled", "wstat"])
def test_modes_zero_bias_bitwise_vs_quantized_oracle(mode):
    """With zero biases the new dataflows must also match the correctly-
    rounded NumPy quantized-chain oracle BITWISE (not just each other)."""
    widths, m = (4, 64, 64, 128), 516
    layers = _mk_layers(widths, np.random.default_rng(1), zero_bias=True)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(m, widths[0])),
                    jnp.float32)
    out = reram_mlp_fused(x, build_program(layers), mode=mode)
    oracle, _ = quant_chain_np(layers, x)
    assert np.array_equal(np.asarray(out), oracle)


def test_mtiled_single_n_tile_stays_weight_stationary():
    """'mtiled' may keep the full N edge (single N-tile): residency has no
    M term AND the plane tile stays resident across stripes — the planner
    must report one plane-tile fetch per layer, and the kernel must match
    whole bitwise."""
    rng = np.random.default_rng(25)
    layers = _mk_layers((16, 256, 256, 512), rng)
    prog = build_program(layers)
    plan = plan_fused_mlp(prog, 700, mode="mtiled")
    assert plan.block_n == prog.d_pad and plan.n_steps == 1
    assert plan.plane_tile_fetches_per_layer == 1
    assert plan.act_hbm_bytes_per_layer == 8 * plan.m_pad * plan.d_pad
    x = jnp.asarray(rng.normal(size=(700, 16)), jnp.float32)
    whole = reram_mlp_fused(x, prog, mode="whole")
    assert bool(jnp.all(reram_mlp_fused(x, prog, mode="mtiled") == whole))


@pytest.mark.parametrize("mode", ["mtiled", "wstat"])
def test_batched_modes_match_vmapped(mode):
    """Batch-in-grid under the new dataflows: per-element scales and
    running maxes must survive the M-tiling / j-outer order (the SMEM
    state resets at each element's first tile)."""
    rng = np.random.default_rng(33)
    layers = _mk_layers((17, 100, 2), rng, zero_bias=True)
    prog = build_program(layers)
    x = jnp.asarray(rng.normal(size=(4, 50, 17))
                    * (10.0 ** np.arange(4))[:, None, None], jnp.float32)
    bat = reram_mlp_fused_batched(x, prog, mode=mode, block_n=128)
    vm = jax.vmap(lambda c: reram_mlp_fused(c, prog, mode=mode,
                                            block_n=128))(x)
    assert bool(jnp.all(bat == vm))


def test_plan_auto_selects_whole_layer_below_budget():
    layers = _mk_layers((4, 64, 64, 128), np.random.default_rng(23))
    prog = build_program(layers)
    plan = plan_fused_mlp(prog, 512)
    assert not plan.tiled and plan.block_n == prog.d_pad == 128
    assert plan.vmem_bytes == plan.whole_bytes <= VMEM_BUDGET_BYTES


def test_plan_auto_selects_tiled_above_budget():
    """Shrinking the budget below the whole-layer residency must flip the
    selector to the largest fitting 128-multiple divisor of d_pad."""
    layers = _mk_layers((512, 512, 1024), np.random.default_rng(24),
                        zero_bias=True)
    prog = build_program(layers)
    whole = fused_vmem_bytes(1024, prog.n_planes, 1024, 128, 1024)
    plan = plan_fused_mlp(prog, 1024, vmem_budget=whole - 1)
    assert plan.tiled and plan.block_n < 1024
    assert 1024 % plan.block_n == 0 and plan.block_n % 128 == 0
    assert plan.vmem_bytes <= whole - 1
    # explicit block sizes are validated against the crossbar geometry
    with pytest.raises(ValueError):
        plan_fused_mlp(prog, 64, block_n=96)
    with pytest.raises(ValueError):
        plan_fused_mlp(prog, 64, block_n=768)    # does not divide 1024
    with pytest.raises(ValueError):
        plan_fused_mlp(prog, 64, block_k=48)
    with pytest.raises(ValueError, match="mode"):
        plan_fused_mlp(prog, 64, mode="striped")
    with pytest.raises(ValueError, match="whole"):
        plan_fused_mlp(prog, 64, mode="whole", block_n=128)


# ---------------------------------------------------------------------------
# planner: auto-selected mode pinned at the budget thresholds
# ---------------------------------------------------------------------------

def _paper_mlp_program(model, layer, zero_bias=True):
    from repro.core import PAPER_MODELS
    spec = PAPER_MODELS[model].layers[layer]
    layers = _mk_layers(spec.mlp, np.random.default_rng(40),
                        zero_bias=zero_bias)
    return build_program(layers), spec.n_centers * spec.n_neighbors


def test_plan_model2_sa1_8192_rows_mtiled_within_budget():
    """THE acceptance geometry: model2 SA-1 (16, 256, 256, 512) at its real
    row count (512 centers x 16 neighbors = 8192). The f32 activation panel
    alone is 16 MB, so no VMEM-panel dataflow can fit at any N edge — the
    selector must land on a fused dataflow that fits: 'mtiled', whose
    residency has no M term. With d_pad=512 a single N-tile fits, so the
    selected plan is weight-stationary too (one plane fetch per layer)."""
    prog, rows = _paper_mlp_program("model2", 0)
    assert rows == 8192
    plan = plan_fused_mlp(prog, rows)
    assert plan.whole_bytes > VMEM_BUDGET_BYTES
    assert plan.mode not in ("whole", "tiled")       # panel-bound
    assert plan.mode == "mtiled"
    assert plan.fits_budget
    assert plan.plane_tile_fetches_per_layer == 1
    # and no act-panel-in-VMEM mode fits at ANY tile edge
    for mode in ("tiled", "wstat"):
        for bn in range(128, prog.d_pad + 1, 128):
            if prog.d_pad % bn == 0:
                assert fused_vmem_bytes(prog.d_pad, prog.n_planes,
                                        plan.m_pad, plan.block_m, bn,
                                        mode=mode) > VMEM_BUDGET_BYTES


def test_plan_model2_sa1_8192_executes_fused():
    """The selected mtiled plan actually runs the 8192-row panel-bound
    shape through ONE fused pallas_call, bitwise-equal to the sequential
    per-layer chain on the zero-bias integer pipeline. (Kept affordable:
    the bitwise mode-equivalence sweep covers the numerics; this pins the
    real acceptance geometry end to end.)"""
    prog, rows = _paper_mlp_program("model2", 0)
    rng = np.random.default_rng(41)
    x = jnp.asarray(rng.normal(size=(rows, prog.widths[0])), jnp.float32)
    fused = reram_mlp_fused(x, prog, final_relu=False)   # auto plan: mtiled
    # compare against the whole-layer dataflow (budget is a residency
    # model, not enforced in interpret mode) — bitwise, biases included
    whole = reram_mlp_fused(x, prog, mode="whole", final_relu=False)
    assert np.array_equal(np.asarray(fused), np.asarray(whole))


def test_plan_model2_sa2_2048_rows_wstat():
    """model2 SA-2 (512, 512, 512, 1024) at 2048 rows: whole busts the
    budget, the N-tiled panel fits, and the selector prefers the j-outer
    weight-stationary dataflow over plain 'tiled' — planes cross HBM once
    per layer instead of once per M-stripe."""
    prog, rows = _paper_mlp_program("model2", 1)
    assert rows == 2048
    plan = plan_fused_mlp(prog, rows)
    assert plan.whole_bytes > VMEM_BUDGET_BYTES
    assert plan.mode == "wstat" and plan.fits_budget
    assert plan.plane_tile_fetches_per_layer == plan.n_steps
    tiled = plan_fused_mlp(prog, rows, mode="tiled", block_n=plan.block_n)
    assert (tiled.plane_tile_fetches_per_layer
            == plan.m_steps * plan.n_steps)
    assert tiled.plane_hbm_bytes_per_layer \
        == plan.m_steps * plan.plane_hbm_bytes_per_layer


def test_plan_auto_prefers_tiled_in_snapshot_panel_band():
    """In the narrow budget band where the int8 snapshot panel pushes
    'wstat' over budget but the one-stripe-snapshot 'tiled' residency still
    fits, the selector must fall back to 'tiled' (act panel stays in VMEM,
    planes re-stream)."""
    layers = _mk_layers((512, 512, 1024), np.random.default_rng(24),
                        zero_bias=True)
    prog = build_program(layers)
    d, p = prog.d_pad, prog.n_planes
    m_pad = 1024
    wstat_min = min(
        fused_vmem_bytes(d, p, m_pad, 128, bn, mode="wstat")
        for bn in range(128, d, 128) if d % bn == 0)
    tiled_min = min(
        fused_vmem_bytes(d, p, m_pad, 128, bn, mode="tiled")
        for bn in range(128, d, 128) if d % bn == 0)
    assert tiled_min < wstat_min
    plan = plan_fused_mlp(prog, m_pad, vmem_budget=wstat_min - 1)
    assert plan.mode == "tiled" and plan.fits_budget


def test_plan_nothing_fits_records_mtiled_miss():
    """When even the M-tiled dataflow cannot fit, the plan records the
    miss (fits_budget False) on the smallest mtiled footprint instead of
    silently pretending."""
    layers = _mk_layers((512, 512, 1024), np.random.default_rng(24))
    prog = build_program(layers)
    plan = plan_fused_mlp(prog, 2048, vmem_budget=1)
    assert plan.mode == "mtiled" and plan.block_n == 128
    assert not plan.fits_budget


def test_plan_mode_pins_respected():
    """Explicit mode= pins the dataflow even when auto would pick another;
    block_n is still auto-sized to the largest fitting edge for it."""
    layers = _mk_layers((4, 64, 64, 128), np.random.default_rng(23))
    prog = build_program(layers)
    for mode in FUSED_MODES:
        plan = plan_fused_mlp(prog, 512, mode=mode)
        assert plan.mode == mode
    assert plan_fused_mlp(prog, 512).mode == "whole"     # auto baseline


# ---------------------------------------------------------------------------
# batch-in-grid: one pallas_call for the whole batch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("widths,b,m,zero_bias", [
    ((17, 100, 2), 4, 50, True),     # zero bias: bitwise vs vmapped
    ((130, 200, 70), 3, 33, False),  # tiled + biases: ~1 ulp
    ((8, 32, 16), 2, 1, False),      # single-row elements (the head shape)
])
def test_batched_matches_vmapped(widths, b, m, zero_bias):
    """Folding the batch into the grid must reproduce the PR-1 vmapped
    path: per-batch-element input scales and running-max requant scales.
    Zero-bias is bitwise; with biases the two compilations agree to ~1
    ulp (FMA contraction)."""
    rng = np.random.default_rng(31)
    layers = _mk_layers(widths, rng, zero_bias=zero_bias)
    prog = build_program(layers)
    # distinct per-element magnitudes so shared-scale bugs cannot hide
    x = jnp.asarray(rng.normal(size=(b, m, widths[0]))
                    * (10.0 ** np.arange(b))[:, None, None], jnp.float32)
    bat = reram_mlp_fused_batched(x, prog, block_n=128)
    vm = jax.vmap(lambda c: reram_mlp_fused(c, prog, block_n=128))(x)
    assert bat.shape == vm.shape == (b, m, widths[-1])
    if zero_bias:
        assert bool(jnp.all(bat == vm))
    else:
        np.testing.assert_allclose(np.asarray(bat), np.asarray(vm),
                                   rtol=1e-5, atol=1e-5)


def test_batched_leading_dims_match_vmapped():
    """(B, M, K, C) aggregation layout — per-element leading dims flatten
    to rows exactly like the unbatched kernel."""
    rng = np.random.default_rng(32)
    prog = build_program(_mk_layers((8, 32, 16), rng))
    x = jnp.asarray(rng.normal(size=(3, 13, 16, 8)), jnp.float32)
    bat = reram_mlp_fused_batched(x, prog)
    vm = jax.vmap(lambda c: reram_mlp_fused(c, prog))(x)
    assert bat.shape == (3, 13, 16, 16)
    np.testing.assert_allclose(np.asarray(bat), np.asarray(vm),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# CrossbarProgram: build-once semantics + round trip
# ---------------------------------------------------------------------------

def test_program_encodes_weights_exactly_once(monkeypatch):
    """The weight-stationary contract: ``encode_planes`` runs once per layer
    at program build, and NEVER in the per-forward hot path — not even at
    trace time."""
    from repro.kernels import program as program_mod
    calls = []
    real = program_mod.encode_planes
    monkeypatch.setattr(program_mod, "encode_planes",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    rng = np.random.default_rng(5)
    layers = _mk_layers((4, 32, 32, 16), rng)
    prog = build_program(layers)
    assert len(calls) == 3                     # once per layer, at build
    x = jnp.asarray(rng.normal(size=(20, 4)), jnp.float32)
    jax.block_until_ready(reram_mlp_fused(x, prog))
    x2 = jnp.asarray(rng.normal(size=(20, 4)), jnp.float32)
    jax.block_until_ready(reram_mlp_fused(x2, prog))
    assert len(calls) == 3                     # zero encodes per forward


@given(st.integers(0, 10_000), st.sampled_from([1, 2, 3]))
@settings(max_examples=10, deadline=None)
def test_program_round_trip(seed, n_layers):
    """decode(encode(w)): the recombined int weights equal quantize(w)
    exactly, and the dequantized floats are within half a quant step."""
    rng = np.random.default_rng(seed)
    widths = rng.integers(1, 70, size=n_layers + 1).tolist()
    layers = _mk_layers(widths, rng)
    prog = build_program(layers)
    assert prog.widths == tuple(widths)
    for lyr, w_int, w_deq, b in zip(layers, prog.int_weights(),
                                    prog.weights(), prog.biases()):
        qi, s = quantize_tensor(lyr["w"])
        assert w_int.shape == lyr["w"].shape
        assert bool(jnp.all(w_int == qi))
        assert float(jnp.max(jnp.abs(w_deq - lyr["w"]))) <= float(s) / 2 + 1e-6
        assert bool(jnp.all(b == lyr["b"]))


def test_program_padding_and_pytree():
    layers = _mk_layers((5, 200, 7), np.random.default_rng(9))
    prog = build_program(layers)
    assert prog.d_pad == 256 and prog.n_layers == 2 and prog.n_planes == 4
    assert prog.planes.shape == (2, 4, 256, 256)
    # padded plane cells are 0 and col_mask kills the garbage columns
    assert int(prog.col_mask[1].sum()) == 7
    # jit/vmap treat it as a pytree with static widths
    leaves, treedef = jax.tree_util.tree_flatten(prog)
    prog2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert prog2.widths == prog.widths


# ---------------------------------------------------------------------------
# end to end: PointNet++ 'reram-fused' backend
# ---------------------------------------------------------------------------

def test_pointnet_fused_backend_matches_per_layer():
    from repro import compile_model
    from repro.core.workload import PointNetConfig, SALayerSpec
    from repro.models import pointnet2 as pn
    cfg = PointNetConfig(name="tiny", n_points=64, layers=(
        SALayerSpec(n_centers=24, n_neighbors=4, in_features=4,
                    mlp=(4, 8, 8, 16)),
        SALayerSpec(n_centers=8, n_neighbors=4, in_features=16,
                    mlp=(16, 16, 16, 32)),
    ))
    params = pn.init_params(jax.random.PRNGKey(0), cfg, n_classes=10)
    model_fused = compile_model(params, cfg, backend="reram-fused")
    model_reram = compile_model(params, cfg, backend="reram")
    cloud = jnp.asarray(np.random.default_rng(11).normal(size=(64, 3)),
                        jnp.float32)
    fused = model_fused.forward(cloud)
    per_layer = model_reram.forward(cloud)
    assert fused.shape == (10,)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(per_layer),
                               rtol=1e-4, atol=1e-4)
    # batch-in-grid front-end over the fused pallas path: matches both the
    # single-cloud fused forward and the PR-1 style vmapped-forward path
    clouds = jnp.stack([cloud, cloud * 0.5])
    batched = model_fused.batched_forward(clouds)
    assert batched.shape == (2, 10)
    np.testing.assert_allclose(np.asarray(batched[0]), np.asarray(fused),
                               rtol=1e-5, atol=1e-5)
    vmapped = jax.vmap(model_fused.forward)(clouds)
    np.testing.assert_allclose(np.asarray(batched), np.asarray(vmapped),
                               rtol=1e-5, atol=1e-5)


def test_pointnet_batched_backend_no_outer_vmap(monkeypatch):
    """``CompiledModel.batched_forward`` on the fused backend must dispatch
    every MLP through the batch-in-grid kernel — one ``pallas_call`` per
    MLP for the whole batch — and never route the batch through the
    unbatched kernel under vmap."""
    from repro import compile_model
    from repro.core.workload import PointNetConfig, SALayerSpec
    from repro.models import backend as backend_mod
    from repro.models import pointnet2 as pn
    cfg = PointNetConfig(name="tiny", n_points=32, layers=(
        SALayerSpec(n_centers=12, n_neighbors=4, in_features=4,
                    mlp=(4, 8, 8, 16)),
        SALayerSpec(n_centers=4, n_neighbors=4, in_features=16,
                    mlp=(16, 16, 16, 32)),
    ))
    params = pn.init_params(jax.random.PRNGKey(1), cfg, n_classes=5)
    model = compile_model(params, cfg, backend="reram-fused")
    clouds = jnp.asarray(np.random.default_rng(13).normal(size=(3, 32, 3)),
                         jnp.float32)
    calls = []
    real = backend_mod.reram_mlp_fused_batched
    monkeypatch.setattr(backend_mod, "reram_mlp_fused_batched",
                        lambda *a, **k: calls.append(a[0].shape) or
                        real(*a, **k))
    monkeypatch.setattr(backend_mod, "reram_mlp_fused",
                        lambda *a, **k: pytest.fail(
                            "batched_forward vmapped the unbatched kernel"))
    out = model.batched_forward(clouds)
    assert out.shape == (3, 5)
    # one batched launch per MLP (2 SA layers + head), batch axis intact
    assert len(calls) == 3
    assert all(shape[0] == 3 for shape in calls)
