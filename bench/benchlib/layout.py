"""Per-block shapes of a 2s-AGCN configuration file, as the configuration
defines them: kept input channels, kept temporal filters, kept taps and the
time length at every block.  The plain reference and the op counts both
read this, so neither takes a table from the program.

A configuration file's ``model`` object holds:

  gcn_joints, gcn_frames, gcn_in_channels, gcn_num_classes,
  gcn_channels, gcn_strides, gcn_kv, gcn_tkernel,
  prune_channel_fracs  per-block kept fraction of the spatial input
                       channels; block 0 is never pruned, and the kept
                       channels are the first round(frac * cin) (empty =
                       no channel pruning)
  cavity_pattern       "cav-<percent>-<variant>" over a loop of 8 filters;
                       like the kept channels it belongs to the pruning
                       plan, so it applies only when prune_channel_fracs
                       is set ("" = every tap kept)
  input_skip           keep one raw frame in input_skip
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np

CAVITY_LOOP = 8


def cavity_mask(name: str, kernel: int, loop: int = CAVITY_LOOP) -> np.ndarray:
    """(loop, kernel) bool mask of kept taps for a named cavity pattern.

    ``cav-P-1`` keeps ``loop*kernel - round(loop*kernel*P/100)`` taps,
    balanced: every tap position is kept floor or ceil of its share of
    times, and each position claims the filters that hold the fewest kept
    taps so far, ties broken by a rotation from the position.  ``cav-P-2``
    is the deliberately unbalanced variant: pairs of positions shift up to
    two of their quota from the odd to the even position first."""
    if not name or name == "none":
        return np.ones((loop, kernel), bool)
    tag, percent, variant = name.split("-")
    if tag != "cav" or variant not in ("1", "2"):
        raise ValueError(f"bad cavity pattern name {name!r}")
    total = loop * kernel
    keep = total - int(round(total * int(percent) / 100.0))
    base, extra = divmod(keep, kernel)
    quota = [base + (1 if c < extra else 0) for c in range(kernel)]
    if variant == "2":
        for c in range(0, kernel - 1, 2):
            move = min(quota[c + 1], loop - quota[c], 2)
            quota[c] += move
            quota[c + 1] -= move
    mask = np.zeros((loop, kernel), bool)
    held = np.zeros(loop, int)
    for c, q in enumerate(quota):
        rows = sorted(range(loop), key=lambda r: (held[r], (r - c) % loop))
        for r in rows[:q]:
            mask[r, c] = True
            held[r] += 1
    return mask


@dataclasses.dataclass(frozen=True)
class Block:
    """One TCN-GCN block at the configuration's kept widths."""

    cin: int            # block input width
    cout: int           # block output width
    stride: int
    n_in: int           # spatial input channels kept (the first n_in)
    n_filters: int      # temporal filters kept (the first n_filters)
    taps: np.ndarray    # (n_filters, K) bool kept taps
    t_in: int           # time length entering the block (one clip)
    t_out: int          # time length leaving it

    @property
    def kept_taps(self) -> int:
        """Kept (filter, tap) pairs of the temporal conv."""
        return int(self.taps.sum())


def blocks(model: dict) -> List[Block]:
    """The configuration's blocks, in order, for one clip of
    ``gcn_frames`` raw frames."""
    channels = list(model["gcn_channels"])
    strides = list(model["gcn_strides"])
    fracs = list(model.get("prune_channel_fracs") or [])
    K = int(model["gcn_tkernel"])
    pattern = cavity_mask((model.get("cavity_pattern") or "") if fracs
                          else "", K)
    cin = int(model["gcn_in_channels"])
    n_in: List[int] = []
    for b, cout in enumerate(channels):
        n_in.append(cin if (b == 0 or not fracs)
                    else max(1, int(round(cin * fracs[b]))))
        cin = cout
    t = -(-int(model["gcn_frames"]) // int(model.get("input_skip", 1)))
    out: List[Block] = []
    cin = int(model["gcn_in_channels"])
    for b, cout in enumerate(channels):
        nf = n_in[b + 1] if (fracs and b + 1 < len(channels)) else cout
        taps = np.tile(pattern, (-(-nf // pattern.shape[0]), 1))[:nf]
        t_out = (t - 1) // strides[b] + 1
        out.append(Block(cin=cin, cout=cout, stride=strides[b], n_in=n_in[b],
                         n_filters=nf, taps=taps, t_in=t, t_out=t_out))
        cin, t = cout, t_out
    return out


def emitted(model: dict, raw_frames: int) -> int:
    """Last-block outputs a streaming session has completed after
    ``raw_frames`` raw frames: block inputs arrive one per kept raw frame,
    and input ``t`` of a block completes output ``(t - pad) / stride``
    once ``t >= pad`` (pad = K // 2, the 'same' padding seen as latency)."""
    pad = int(model["gcn_tkernel"]) // 2
    n = -(-int(raw_frames) // int(model.get("input_skip", 1)))
    for s in model["gcn_strides"]:
        n = 0 if n <= pad else (n - 1 - pad) // int(s) + 1
    return n


def first_logit_frames(model: dict) -> int:
    """Raw frames until the first last-block output completes."""
    n = 1
    while emitted(model, n) == 0:
        n += 1
    return n


def stream_shapes(model: dict) -> Tuple[int, int, int]:
    """(joints, in_channels, classes)."""
    return (int(model["gcn_joints"]), int(model["gcn_in_channels"]),
            int(model["gcn_num_classes"]))
