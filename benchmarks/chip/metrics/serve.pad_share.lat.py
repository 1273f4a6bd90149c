"""Share of the serving step's rows that were padding: 1 - sum(batch_real) / sum(batch_bucket) over the window's serve.run_batch spans."""
from chipbench.spans import pad_share as read  # noqa: F401
