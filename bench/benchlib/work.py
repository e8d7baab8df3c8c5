"""Operations and bytes the algorithm needs, from the configuration file
alone (kept widths, kept taps, input skip), for one stream.

Counted: graph aggregation (K subsets of V x V over the kept input
channels), the spatial 1x1 convolutions (K of them, kept inputs to full
outputs), the temporal convolution at its kept filters and kept taps, the
residual 1x1 projections, and the fc head — 2 operations per multiply-add.
Not counted: batch norm, ReLU, padding, the RFC inter-layer format and any
one-hot compaction, which are not model work: a change that removes them
raises the utilization honestly, and no count can exceed what a kernel
really computes.

Bytes are float32 activations read and written by each kernel family once,
plus its weights once per program execution ("dispatch")."""
from __future__ import annotations

from typing import Dict

from benchlib import layout

F32 = 4


def per_row(model: dict) -> Dict[str, float]:
    """Operations for one row (one body) of one ``gcn_frames`` clip, by
    part: sconv (aggregation + spatial 1x1), tconv, proj, fc."""
    V, K = int(model["gcn_joints"]), int(model["gcn_kv"])
    ops = {"sconv": 0.0, "tconv": 0.0, "proj": 0.0, "fc": 0.0}
    blocks = layout.blocks(model)
    for b in blocks:
        ops["sconv"] += 2.0 * b.t_in * K * V * b.n_in * (V + b.cout)
        ops["tconv"] += 2.0 * b.t_out * V * b.cout * b.kept_taps
        if b.cin != b.cout:
            ops["proj"] += 2.0 * b.t_in * V * b.cin * b.cout      # down
        if b.cin != b.cout or b.stride != 1:
            ops["proj"] += 2.0 * b.t_out * V * b.cin * b.cout     # shortcut
    ops["fc"] = 2.0 * blocks[-1].cout * int(model["gcn_num_classes"])
    return ops


def act_bytes_per_row(model: dict) -> Dict[str, float]:
    """Activation bytes one row of one clip moves through each family."""
    V = int(model["gcn_joints"])
    out = {"sconv": 0.0, "tconv": 0.0}
    for b in layout.blocks(model):
        out["sconv"] += F32 * b.t_in * V * (b.n_in + b.cout)
        out["tconv"] += F32 * V * (b.t_in * b.cout + b.t_out * b.n_filters)
    return out


def weight_bytes(model: dict) -> Dict[str, float]:
    """Weight bytes each family reads once per dispatch."""
    V, K = int(model["gcn_joints"]), int(model["gcn_kv"])
    out = {"sconv": 0.0, "tconv": 0.0}
    for b in layout.blocks(model):
        out["sconv"] += F32 * K * (b.n_in * b.cout + V * V)
        out["tconv"] += F32 * b.cout * b.kept_taps
    return out


def model_ops_per_row(model: dict) -> float:
    """All counted operations of one row of one clip, one stream."""
    return float(sum(per_row(model).values()))


def window_work(model: dict, streams: int, rows_clips: float,
                dispatches: int) -> Dict[str, Dict[str, float]]:
    """Family operations and bytes for ``rows_clips`` row-clips of work
    (a live frame is 1/gcn_frames of a row-clip) run in ``dispatches``
    program executions, over ``streams`` streams."""
    ops, act, wts = per_row(model), act_bytes_per_row(model), \
        weight_bytes(model)
    return {f: {"ops": streams * rows_clips * ops[f],
                "bytes": streams * (rows_clips * act[f]
                                    + dispatches * wts[f])}
            for f in ("sconv", "tconv")}
