"""Roofline bound of the gathers over the gather kernel's device time."""
from chipbench.readers import gather_roofline as read  # noqa: F401
