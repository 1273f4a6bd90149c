"""Median over the window's serve.run_batch spans of the summed wall time of their serve.plan spans."""
from chipbench.spans import plan_host_ms_per_step as read  # noqa: F401
