"""Device time of the programs run outside the serving step (plan builds), per cloud served."""
from chipbench.readers import plan_device_ms_per_cloud as read  # noqa: F401
