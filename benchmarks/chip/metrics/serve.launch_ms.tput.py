"""Median delay from a serve.dispatch span's start to the first device op of the serving step it launched."""
from chipbench.spans import launch_ms as read  # noqa: F401
