"""Share of the traced window in which the device is idle while the loop thread is inside a serve.step span."""
from chipbench.spans import idle_in_serve_share as read  # noqa: F401
