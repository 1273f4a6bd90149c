"""Model FLOPs of the clouds served (work_msg.py, 7.864 GFLOP per MSG cloud) over the summed wall time of their steps, as a share of the int8 peak."""
from chipbench.readers_msg import step_mfu as read  # noqa: F401
