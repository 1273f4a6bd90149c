"""The main-path Pallas kernels compile for a TPU v5e, at the published
widths, with the interpreter off — checked without a chip.

This is the only test file that describes a chip. The ``v5e:2x2``
topology is built inside a module-scoped fixture (never while a module is
imported): only one process may hold the TPU library, and the test
workers must all collect the same tests. Each test lowers a kernel for
one described device and compiles it with the TPU compiler, which refuses
what Mosaic refuses on the chip (block tiling, dot dtypes, scoped VMEM).

``repro.kernels.platform.interpret_mode`` reads the platform from
``jax.default_backend()``, which stays "cpu" here; the ``on_tpu`` fixture
makes it answer "tpu" for these compiles and drops JAX's trace caches on
both sides, so no interpret-mode trace leaks in or compiled trace leaks
out.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.workload import PAPER_MODELS, POINTNET2_MSG
from repro.kernels import (aggregate_diff, aggregate_diff_batched,
                           build_program, fps_update, plan_fused_mlp,
                           requantize, reram_matmul_int,
                           reram_mlp_fused_batched)
from repro.kernels.aggregate import index_chunks

BATCH = 4          # the serving tier's largest batch bucket in chip_smoke


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def on_tpu(one_chip):
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        yield one_chip
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; return the optimized HLO."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the kernel did not lower to Mosaic"
    return text


@pytest.mark.parametrize("model,layer", [("model0", 0), ("model2", 1)],
                         ids=["model0-sa0-c4", "model2-sa1-c512"])
def test_gather_compiles(on_tpu, model, layer):
    cfg = PAPER_MODELS[model]
    spec = cfg.layers[layer]
    n_in = cfg.n_points if layer == 0 else cfg.layers[layer - 1].n_centers
    _compile(aggregate_diff_batched,
             _spec(on_tpu, (BATCH, n_in, spec.in_features), jnp.float32),
             _spec(on_tpu, (BATCH, spec.n_centers, spec.n_neighbors),
                   jnp.int32),
             _spec(on_tpu, (BATCH, spec.n_centers), jnp.int32))


def test_gather_compiles_chunked_at_msg_widths(on_tpu):
    """MSG SA-1's K = 128 branch at the serving tier's largest batch of 16:
    its index stream (16 x 512 x 129 words, 4 MiB) is past the v5e's SMEM,
    so it runs as several launches, each compiled here, with the centre
    rows ``[p ; 0]`` as an operand of their own."""
    spec = POINTNET2_MSG.layers[0]
    c, k = 3 + spec.in_features, spec.branches[-1].n_neighbors
    rows = _spec(on_tpu, (16, POINTNET2_MSG.n_points, c), jnp.float32)
    text = _compile(aggregate_diff_batched, rows,
                    _spec(on_tpu, (16, spec.n_centers, k), jnp.int32),
                    _spec(on_tpu, (16, spec.n_centers), jnp.int32), rows)
    assert text.count("tpu_custom_call") == len(
        index_chunks(16, spec.n_centers, k)) > 1


def _msg_mlps():
    """(id, widths, rows per cloud) of every MLP of the MSG classifier."""
    cfg = POINTNET2_MSG
    out = [(f"sa{i}.b{b}-k{br.n_neighbors}", br.mlp,
            spec.n_centers * br.n_neighbors)
           for i, spec in enumerate(cfg.layers)
           for b, br in enumerate(spec.branches)]
    return out + [("sa_all", cfg.group_all.mlp, cfg.layers[-1].n_centers),
                  ("head", (cfg.out_features, *cfg.head, 40), 1)]


@pytest.mark.parametrize("widths,rows", [m[1:] for m in _msg_mlps()],
                         ids=[m[0] for m in _msg_mlps()])
def test_fused_mlp_compiles_at_msg_widths(on_tpu, widths, rows):
    """The dataflow ``plan_fused_mlp`` picks for each MSG MLP at its rows
    per cloud compiles at batch 16."""
    layers = [{"w": jnp.zeros((k, n), jnp.float32),
               "b": jnp.zeros((n,), jnp.float32)}
              for k, n in zip(widths[:-1], widths[1:])]
    prog = jax.eval_shape(build_program, layers)
    mode = plan_fused_mlp(prog, rows).mode
    prog = jax.tree.map(lambda a: _spec(on_tpu, a.shape, a.dtype), prog)
    x = _spec(on_tpu, (16, rows, widths[0]), jnp.float32)
    _compile(lambda x, p: reram_mlp_fused_batched(x, p, mode=mode), x, prog)


@pytest.mark.parametrize("model,layer,mode,picked", [
    ("model0", 0, "whole", True),
    ("model2", 0, "mtiled", True),
    ("model2", 1, "wstat", True),
    ("model0", 1, "tiled", False),
], ids=["model0-sa0", "model2-sa0", "model2-sa1", "model0-sa1-tiled"])
def test_fused_mlp_compiles(on_tpu, model, layer, mode, picked):
    """The dataflow ``plan_fused_mlp`` picks at the layer's real row count
    compiles within Mosaic's scoped VMEM; 'tiled', which no paper layer
    picks, is forced where it splits the width into several N-tiles."""
    spec = PAPER_MODELS[model].layers[layer]
    layers = [{"w": jnp.zeros((k, n), jnp.float32),
               "b": jnp.zeros((n,), jnp.float32)}
              for k, n in spec.mlp_shapes]
    prog = jax.eval_shape(build_program, layers)
    rows = spec.n_centers * spec.n_neighbors
    assert (plan_fused_mlp(prog, rows).mode == mode) == picked
    if not picked:              # forced: really several N-tiles
        assert plan_fused_mlp(prog, rows, mode=mode).block_n < prog.d_pad
    prog = jax.tree.map(lambda a: _spec(on_tpu, a.shape, a.dtype), prog)
    x = _spec(on_tpu, (BATCH, spec.n_centers, spec.n_neighbors,
                       spec.in_features), jnp.float32)
    _compile(lambda x, p: reram_mlp_fused_batched(x, p, mode=mode), x, prog)


def test_reram_matmul_int_compiles(on_tpu):
    _compile(reram_matmul_int, _spec(on_tpu, (256, 256), jnp.int8),
             _spec(on_tpu, (4, 256, 256), jnp.int8))


def test_requantize_compiles(on_tpu):
    """The fused kernel's requantizer alone, at model2 SA-0's widest
    inter-layer activation."""
    _compile(requantize, _spec(on_tpu, (512 * 16, 256), jnp.float32))


@pytest.mark.parametrize("kernel", ["fps_update", "aggregate_diff"])
def test_exported_off_path_kernel_compiles(on_tpu, kernel):
    """The exported kernels the served path does not run still compile, at
    model0 SA-0's sizes."""
    n, spec = 1024, PAPER_MODELS["model0"].layers[0]
    if kernel == "fps_update":
        _compile(fps_update, _spec(on_tpu, (3, n), jnp.float32),
                 _spec(on_tpu, (3, 1), jnp.float32),
                 _spec(on_tpu, (1, n), jnp.float32))
    else:
        _compile(aggregate_diff,
                 _spec(on_tpu, (n, spec.in_features), jnp.float32),
                 _spec(on_tpu, (spec.n_centers, spec.n_neighbors),
                       jnp.int32),
                 _spec(on_tpu, (spec.n_centers,), jnp.int32))
