"""Roofline bound of every branch's, the group-all and the head's fused MLP (work_msg.py) over the fused MLP kernels' device time."""
from chipbench.readers_msg import mlp_roofline as read  # noqa: F401
