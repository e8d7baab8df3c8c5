"""Roofline-term computation from the static HLO analysis (hlo_cost.py).

  compute term    = HLO_FLOPs / peak_FLOP/s          (per chip)
  memory term     = HLO_bytes / HBM_bw               (per chip)
  collective term = collective_bytes / link_bw       (per chip)

All inputs are per-chip (the analyzed module is the post-GSPMD per-device
program), so no further division by chip count is needed; the equivalent
whole-system statement divides totals by chips — identical numbers.
"""
from __future__ import annotations

from typing import Dict

from repro.launch.mesh import peak_rates


def roofline_terms(
    hlo_cost: Dict,
    model_flops_total: float,
    chips: int,
    device_kind: str,
    model_bytes_total: float = 0.0,
) -> Dict:
    """Roofline terms of one compiled step against the published peaks of
    ``device_kind`` (:func:`repro.launch.mesh.peak_rates`)."""
    peaks = peak_rates(device_kind)
    peak_flops, hbm_bw, ici_bw = (peaks["flops_bf16"], peaks["hbm_bw"],
                                  peaks["ici_bw"])
    flops = float(hlo_cost.get("flops", 0.0))
    hbm_bytes = float(hlo_cost.get("bytes", 0.0))
    coll = float(hlo_cost.get("collective_bytes", 0.0))

    t_compute = flops / peak_flops
    t_memory = hbm_bytes / hbm_bw
    t_collective = coll / ici_bw
    dominant = max(
        ("compute", t_compute), ("memory", t_memory),
        ("collective", t_collective), key=lambda kv: kv[1],
    )[0]
    bound = max(t_compute, t_memory, t_collective, 1e-30)
    model_per_chip = model_flops_total / chips
    # The ideal step time is bounded below by BOTH the model's mandatory
    # FLOPs at peak AND its mandatory HBM traffic (params, caches) at full
    # bandwidth — a memory-bound decode step at full HBM bw IS at roofline.
    ideal = max(model_per_chip / peak_flops,
                (model_bytes_total / chips) / hbm_bw)
    return {
        "hlo_flops": flops,
        "hlo_bytes": hbm_bytes,
        "collective_bytes": coll,
        "collectives": hlo_cost.get("collectives", {}),
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "dominant": dominant,
        "model_flops_per_chip": model_per_chip,
        "model_bytes_per_chip": model_bytes_total / chips,
        "useful_flop_ratio": (model_per_chip / flops) if flops else 0.0,
        "ideal_s": ideal,
        # fraction of the roofline-ideal step time actually achievable given
        # the dominant term — the §Perf score
        "roofline_fraction": ideal / bound,
    }
