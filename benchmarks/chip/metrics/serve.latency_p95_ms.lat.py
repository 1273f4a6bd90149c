"""95th percentile latency, due time to logits ready, over every request
due in the (traced) window: the tail beside the median that the cell
bounds."""
from chipbench.readers import latency_ms


def read(run):
    return latency_ms(run, 95)
