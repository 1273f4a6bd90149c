"""Roofline bound of every branch's gather (work_msg.py) over the gather kernel's device time."""
from chipbench.readers_msg import gather_roofline as read  # noqa: F401
