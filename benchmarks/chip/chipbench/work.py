"""Operations and bytes that PointNet++'s algorithm needs, from shapes.

Every roofline and ``mfu`` number divides these by measured time. They
count the algorithm's work, not what one implementation happens to do:

- an SA MLP does ``2 * rows * sum(k * n)`` operations at PointNet++'s own
  row count ``M * K`` (the head: one row); the four bit-plane dots of the
  crossbar emulation, padding to the crossbar edge and the rows of a
  padded batch are not counted;
- an MLP stage moves at least its int8 input rows, its int8 weights and
  the float32 per-centre outputs that the max over neighbours leaves (an
  implementation may write more, none can write less);
- a gather reads each referenced feature row at least once (at most
  ``min(N, M * (K + 1))`` of them) and writes its ``M * K`` difference
  rows, at the float32 feature dtype.

So a kernel's roofline share is bounded by 100 % whatever a later change
fuses, collapses or elides.
"""
from __future__ import annotations

FEATURE_BYTES = 4     # float32 features and difference rows
INT8_BYTES = 1


def mlp_macs(widths) -> int:
    return sum(k * n for k, n in zip(widths[:-1], widths[1:]))


def stages(cfg: dict) -> list[dict]:
    """One entry per kernel launch of one cloud's forward pass, in order:
    each SA layer's gather and MLP, then the head MLP."""
    out = []
    n_in = cfg["n_points"]
    for i, layer in enumerate(cfg["layers"]):
        m, k, widths = layer["n_centers"], layer["n_neighbors"], layer["mlp"]
        out.append({"kind": "gather", "key": f"sa{i}",
                    "rows_read": min(n_in, m * (k + 1)),
                    "rows_written": m * k, "width": widths[0]})
        out.append({"kind": "mlp", "key": f"sa{i}", "rows": m * k,
                    "reduce": k, "widths": tuple(widths)})
        n_in = m
    out.append({"kind": "mlp", "key": "head", "rows": 1, "reduce": 1,
                "widths": tuple(cfg["head"])})
    return out


def stage_work(stage: dict, clouds: int = 1) -> tuple[float, float]:
    """(operations, bytes) of one launch of ``stage`` over ``clouds``
    clouds; an MLP's weights are read once per launch."""
    if stage["kind"] == "gather":
        rows = stage["rows_read"] + stage["rows_written"]
        return 0.0, float(clouds * rows * stage["width"] * FEATURE_BYTES)
    widths, rows = stage["widths"], stage["rows"]
    ops = 2.0 * clouds * rows * mlp_macs(widths)
    per_cloud = (rows * widths[0] * INT8_BYTES
                 + rows // stage["reduce"] * widths[-1] * FEATURE_BYTES)
    return ops, float(clouds * per_cloud + mlp_macs(widths) * INT8_BYTES)


def model_flops_per_cloud(cfg: dict) -> float:
    """2 x the MLP multiply-adds of one cloud at PointNet++'s row counts."""
    return sum(stage_work(s)[0] for s in stages(cfg) if s["kind"] == "mlp")


def bound_seconds(ops: float, nbytes: float, peak_ops: float,
                  bytes_per_s: float) -> float:
    """The least time the chip could take: the larger of the compute and
    the bandwidth bound."""
    return max(ops / peak_ops, nbytes / bytes_per_s)


def kind_bound_seconds(cfg: dict, kind: str, batches, peak_ops: float,
                       bytes_per_s: float) -> float:
    """Summed roofline bound of every ``kind`` stage ('mlp' or 'gather')
    over served batches; ``batches`` holds each batch's count of real
    clouds (one launch per stage per batch)."""
    return sum(bound_seconds(*stage_work(s, b), peak_ops, bytes_per_s)
               for b in batches if b
               for s in stages(cfg) if s["kind"] == kind)
