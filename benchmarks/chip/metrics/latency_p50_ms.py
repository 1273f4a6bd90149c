"""Median latency, due time to logits ready, over every request due in the window."""
from chipbench.readers import latency_ms


def read(run):
    return latency_ms(run, 50)
