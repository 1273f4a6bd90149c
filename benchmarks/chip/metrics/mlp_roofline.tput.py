"""Roofline bound of the fused MLP stages over the fused MLP kernels' device time."""
from chipbench.readers import mlp_roofline as read  # noqa: F401
