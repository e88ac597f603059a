"""Roofline terms from a dry-run analysis record.

Hardware model: the peaks of the chip named by its ``device_kind``
(:data:`PEAKS`); a kind that is not in the table is an error.

All analyzer quantities are *per device* (the SPMD module is the per-device
program), so:

  compute_s    = flops / peak_flops
  memory_s     = hbm_bytes / hbm_bw
  collective_s = collective_bytes / link_bw     (operand-size sum, spec defn)

MODEL_FLOPS uses the 6*N*D / 2*N*D convention (train / inference) with
N = active params (MoE-aware), D = tokens per step — the ratio against
compiled dot-FLOPs exposes remat recompute, causal waste, and dispatch
overhead.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

from repro.configs.base import ModelConfig

@dataclass(frozen=True)
class Peaks:
    flops: float    # bf16 FLOP/s per chip
    hbm_bw: float   # HBM bytes/s per chip
    link_bw: float  # ICI bytes/s per link


# keyed by jax's ``device_kind``. TPU v5e: 197 TFLOP/s bf16 and 819 GB/s of
# HBM (Google Cloud documentation, "TPU v5e"); 1,600 Gbit/s of ICI per chip
# over its four links, 50 GB/s each
PEAKS = {"TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, link_bw=50e9)}


def peaks_for(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak figures for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def model_flops(cfg: ModelConfig, mode: str, tokens: int) -> float:
    """6*N_active*D for training, 2*N_active*D for inference (global)."""
    n = cfg.active_param_count()
    mult = 6.0 if mode == "train" else 2.0
    return mult * n * tokens


def roofline_from_report(
    cfg: ModelConfig, report: Dict, *, chips: int, mode: str, tokens: int,
    device_kind: str,
) -> Dict:
    peaks = peaks_for(device_kind)
    flops = report["flops"]
    dot_flops = report["dot_flops"]
    hbm = report["hbm_bytes"]
    coll = report["collective_bytes"]
    coll_traffic = report["collective_traffic_bytes"]
    compute_s = flops / peaks.flops
    memory_s = hbm / peaks.hbm_bw
    collective_s = coll / peaks.link_bw
    terms = {"compute_s": compute_s, "memory_s": memory_s, "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    # TPU-fusion-aware memory estimate (elementwise fused away); falls back
    # to the conservative bound for artifacts predating the field
    memory_fused_s = report.get("hbm_bytes_fused", hbm) / peaks.hbm_bw
    mf = model_flops(cfg, mode, tokens)
    hlo_global_flops = flops * chips
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "memory_fused_s": memory_fused_s,
        "collective_s": collective_s,
        "collective_traffic_s": coll_traffic / peaks.link_bw,
        "dominant": dominant,
        "model_flops_global": mf,
        "hlo_flops_global": hlo_global_flops,
        "useful_flops_ratio": mf / hlo_global_flops if hlo_global_flops else 0.0,
        "step_time_bound_s": max(terms.values()),
        # fraction of the compute roofline actually achieved if the dominant
        # term were the wall clock (MODEL_FLOPS / (chips*peak) / bound)
        "roofline_fraction": (
            (mf / (chips * peaks.flops)) / max(max(terms.values()), 1e-30)
        ),
    }
