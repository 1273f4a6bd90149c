"""Clouds per served batch over the window (the servable's counters)."""
from chipbench.readers import batch_mean as read  # noqa: F401
