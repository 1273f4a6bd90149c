"""Median time from a request's due time to the start of the step that served it."""
from chipbench.readers import queue_ms as read  # noqa: F401
