"""Operations and bytes of the published C_k (``agcn2s-ck``), from the
configuration file alone, for one stream; the families of
:mod:`benchlib.work` keep their counts, and this module adds C_k's terms.

Counted, per block and row (one body of one clip), 2 operations per
multiply-add: ``proj``, the 2K 1x1 convolutions theta_k/phi_k (kept input
channels to Ce = C_out/4) over every frame and joint; ``sim``, the K
similarities, a (V, Ce*T) x (Ce*T, V) contraction each.  Not counted: the
biases, the softmax and the graph sum A_k + B_k + C_k, which are not
matmul work (as batch norm is not in :mod:`benchlib.work`).

Bytes are float32, read or written once by the kernel that moves them:
``ck_sim`` reads every theta/phi embedding and writes K graphs of V x V
per row; ``graph_sconv_rows`` moves :mod:`benchlib.work`'s sconv
activations and weights, plus each row's K graphs."""
from __future__ import annotations

from typing import Dict

from benchlib import layout, work

F32 = 4


def ck_ops_per_row(model: dict) -> Dict[str, float]:
    """Operations of C_k for one row of one clip: ``proj`` and ``sim``."""
    V, K = int(model["gcn_joints"]), int(model["gcn_kv"])
    ops = {"proj": 0.0, "sim": 0.0}
    for b in layout.blocks(model):
        ce = b.cout // 4
        ops["proj"] += 2 * K * 2.0 * b.t_in * V * b.n_in * ce
        ops["sim"] += K * 2.0 * V * V * ce * b.t_in
    return ops


def sim_bytes_per_row(model: dict) -> float:
    """Bytes the similarity kernel moves for one row of one clip."""
    V, K = int(model["gcn_joints"]), int(model["gcn_kv"])
    return sum(F32 * K * (2.0 * V * (b.cout // 4) * b.t_in + V * V)
               for b in layout.blocks(model))


def graph_bytes_per_row(model: dict) -> float:
    """Bytes of one row's K per-row graphs, over every block."""
    V, K = int(model["gcn_joints"]), int(model["gcn_kv"])
    return F32 * K * V * V * len(layout.blocks(model))


def model_ops_per_row(model: dict) -> float:
    """All counted operations of one row of one clip, one stream, C_k
    included."""
    return work.model_ops_per_row(model) + sum(ck_ops_per_row(model).values())


def window_work(model: dict, streams: int, rows_clips: float,
                dispatches: int) -> Dict[str, Dict[str, float]]:
    """Operations and bytes of the ``ck_sim`` kernel (``ck``) and of the
    per-row ``graph_sconv_rows`` kernel (``sconv_rows``) for
    ``rows_clips`` row-clips run in ``dispatches`` program executions,
    over ``streams`` streams."""
    base = work.window_work(model, streams, rows_clips, dispatches)["sconv"]
    return {
        "ck": {"ops": streams * rows_clips * ck_ops_per_row(model)["sim"],
               "bytes": streams * rows_clips * sim_bytes_per_row(model)},
        "sconv_rows": {"ops": base["ops"],
                       "bytes": base["bytes"] + streams * rows_clips
                       * graph_bytes_per_row(model)}}
