"""Operations and bytes that PointNet++ multi-scale grouping (MSG) needs,
from shapes, counted as ``work.py`` counts them for one scale: per branch
of each MSG layer a gather and an MLP at the branch's own ``M * K_b``
rows, then the group-all MLP over the last layer's points and the head.

A branch's gather reads each referenced row of ``[p ; f]`` (and the
centres' ``[p ; 0]``) at least once, at most ``min(N, M * (K_b + 1))``
of them, and writes its ``M * K_b`` grouped rows; its MLP does
``2 * M * K_b * sum(k * n)`` operations. So every share stays within
100 % whatever a later change fuses, collapses or elides.
"""
from __future__ import annotations

from . import work


def stages(cfg: dict) -> list[dict]:
    """One entry per kernel stage of one cloud's forward pass, in order:
    each branch's gather and MLP, the group-all MLP, the head."""
    out = []
    n_in = cfg["n_points"]
    for i, layer in enumerate(cfg["layers"]):
        m = layer["n_centers"]
        for b, br in enumerate(layer["branches"]):
            k, widths = br["n_neighbors"], br["mlp"]
            key = f"sa{i}.b{b}"
            out.append({"kind": "gather", "key": key,
                        "rows_read": min(n_in, m * (k + 1)),
                        "rows_written": m * k, "width": widths[0]})
            out.append({"kind": "mlp", "key": key, "rows": m * k,
                        "reduce": k, "widths": tuple(widths)})
        n_in = m
    out.append({"kind": "mlp", "key": "sa_all", "rows": n_in,
                "reduce": n_in, "widths": tuple(cfg["group_all"]["mlp"])})
    out.append({"kind": "mlp", "key": "head", "rows": 1, "reduce": 1,
                "widths": tuple(cfg["head"])})
    return out


def model_flops_per_cloud(cfg: dict) -> float:
    """2 x the MLP multiply-adds of one cloud at PointNet++'s row counts."""
    return sum(work.stage_work(s)[0] for s in stages(cfg)
               if s["kind"] == "mlp")


def kind_bound_seconds(cfg: dict, kind: str, batches, peak_ops: float,
                       bytes_per_s: float) -> float:
    """Summed roofline bound of every ``kind`` stage ('mlp' or 'gather')
    over served batches; ``batches`` holds each batch's count of real
    clouds."""
    return sum(work.bound_seconds(*work.stage_work(s, b), peak_ops,
                                  bytes_per_s)
               for b in batches if b
               for s in stages(cfg) if s["kind"] == kind)
