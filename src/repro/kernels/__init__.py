"""Pallas TPU kernels for the compute hot-spots the paper optimizes.

- ``reram_mlp``  : bit-sliced weight-stationary INT8 matmul (contribution 1)
- ``program``    : CrossbarProgram — weights quantized + plane-encoded once
                   at "program time", resident thereafter (the crossbar
                   programming lifecycle)
- ``fused_mlp``  : whole multi-layer MLP in ONE pallas_call, inter-layer
                   activations in VMEM scratch (inter-layer coordination
                   applied inside feature computation)
- ``aggregate``  : scalar-prefetch neighbor gather + difference (the
                   irregular access that contributions 2/3 optimize)
- ``fps_update`` : FPS distance relaxation (front-end hot loop)

Every kernel has a pure-jnp oracle in ``ref.py`` and a jit'd public wrapper.
``platform.interpret_mode`` decides, from the platform alone, how every
``pallas_call`` runs: in the Pallas interpreter on CPU (the test suite),
compiled by Mosaic on a TPU (``chip_smoke.py`` serves model0-2 that way;
``tests/test_tpu_compile.py`` compiles the main-path kernels for a v5e
without one).
"""
from .fused_mlp import (chain_quantizer, reram_mlp_fused,
                        reram_mlp_fused_batched, requantize)
from .ops import (aggregate_diff, aggregate_diff_batched,
                  count_dma_elisions, encode_planes, fps, fps_update,
                  quantize_tensor, reram_linear)
from .platform import interpret_mode
from .program import (FUSED_MODES, CrossbarProgram, FusedPlan, build_program,
                      fused_vmem_bytes, plan_fused_mlp)
from .reram_mlp import reram_matmul_int

__all__ = [
    "CrossbarProgram", "FUSED_MODES", "FusedPlan", "aggregate_diff",
    "chain_quantizer",
    "aggregate_diff_batched",
    "build_program", "count_dma_elisions", "encode_planes", "fps",
    "fps_update", "fused_vmem_bytes", "interpret_mode", "plan_fused_mlp",
    "quantize_tensor", "requantize", "reram_linear", "reram_matmul_int",
    "reram_mlp_fused", "reram_mlp_fused_batched",
]
