"""Clouds whose logits were ready inside the window, over the window's seconds."""
from chipbench.readers import clouds_per_s as read  # noqa: F401
