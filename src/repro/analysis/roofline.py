"""Roofline terms from per-device HLO cost (see hlo.py), against the
published peaks of the device the program runs on.

All three terms are per-chip seconds for one step:
  compute_s    = flops_per_device / peak_flops
  memory_s     = hbm_bytes_per_device / hbm_bw
  collective_s = collective_link_bytes_per_device / (links * link_bw)

The dominant term lower-bounds the step time; fraction-of-roofline for the
iteration log is dominant / sum (how close the step is to being purely
bound by its bottleneck).
"""
from __future__ import annotations

import dataclasses

from repro.models.config import ModelConfig


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12      # bf16 / chip
    hbm_bw: float = 819e9           # bytes/s
    link_bw: float = 50e9           # bytes/s/link (ICI)
    n_links: int = 4                # v5e: 4 usable ICI links per chip (2D)
    vmem_bytes: int = 128 * 2 ** 20
    hbm_bytes: int = 16 * 2 ** 30


# Peak table keyed by ``jax.Device.device_kind``. TPU v5e: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s,
# 1,600 Gbit/s of ICI per chip (4 links x 50 GB/s).
PEAKS = {"TPU v5 lite": HW()}
V5E = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> HW:
    """Published peaks of one device kind; a kind missing from ``PEAKS``
    is an error, never a silent default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak table for device kind {device_kind!r}; "
                         f"known kinds: {sorted(PEAKS)}") from None


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    flops: float
    hbm_bytes: float
    collective_bytes: float
    model_flops: float = 0.0

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (remat / redundancy waste detector)."""
        return self.model_flops / self.flops if self.flops else 0.0

    def as_dict(self) -> dict:
        return {"compute_s": self.compute_s, "memory_s": self.memory_s,
                "collective_s": self.collective_s, "dominant": self.dominant,
                "flops": self.flops, "hbm_bytes": self.hbm_bytes,
                "collective_bytes": self.collective_bytes,
                "model_flops": self.model_flops,
                "useful_ratio": self.useful_ratio}


def roofline_terms(cost, hw: HW = V5E, model_flops: float = 0.0
                   ) -> RooflineTerms:
    """cost: analysis.hlo.ModuleCost (per-device numbers)."""
    return RooflineTerms(
        compute_s=cost.flops / hw.peak_flops,
        memory_s=cost.hbm_bytes / hw.hbm_bw,
        collective_s=cost.collective_bytes / (hw.n_links * hw.link_bw),
        flops=cost.flops,
        hbm_bytes=cost.hbm_bytes,
        collective_bytes=cost.collective_bytes,
        model_flops=model_flops,
    )


def model_flops(cfg: ModelConfig, n_tokens: int, kind: str) -> float:
    """MODEL_FLOPS = 6*N*D (train) or 2*N*D (fwd-only) with N = active
    params (MoE top-k counts only routed-active experts)."""
    n = cfg.active_param_count()
    mult = 6.0 if kind == "train" else 2.0
    return mult * n * n_tokens
