"""Model FLOPs of the clouds served over the summed wall time of their steps, as a share of the peak."""
from chipbench.readers import step_mfu as read  # noqa: F401
