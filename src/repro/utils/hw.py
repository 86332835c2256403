"""Hardware constants and roofline arithmetic for the TPU v5e target.

The paper calibrates its bandwidth-bound performance model against measured
STREAM Triad numbers (Woodcrest 6.5 GB/s, Shanghai 20 GB/s, Nehalem 35 GB/s).
Our target is a TPU v5e pod; the equivalent calibration constants are given
by the assignment:

    peak compute  : 197 TFLOP/s bf16 per chip
    HBM bandwidth : 819 GB/s per chip
    ICI link      : ~50 GB/s per link per chip

All roofline terms in this repo are computed through this module so that the
constants live in exactly one place.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass


@dataclass(frozen=True)
class ChipSpec:
    name: str
    peak_flops_bf16: float  # FLOP/s
    peak_flops_fp32: float  # FLOP/s (VPU-bound for non-MXU ops)
    hbm_bytes_per_s: float
    hbm_bytes: int
    ici_bytes_per_s_per_link: float
    ici_links: int  # links per chip on a 2D torus (v5e: 4; 3D torus v4: 6)
    vmem_bytes: int
    smem_bytes: int = 1024**2
    mxu_shape: tuple = (128, 128)
    vpu_lanes: int = 128
    vpu_sublanes: int = 8


# TPU v5e (the assignment's target). peak_flops_fp32 is the VPU fp32 rate
# (~1/4 of bf16 MXU peak is a reasonable planning number for elementwise).
TPU_V5E = ChipSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    peak_flops_fp32=197e12 / 4,
    hbm_bytes_per_s=819e9,
    hbm_bytes=16 * 1024**3,
    ici_bytes_per_s_per_link=50e9,
    ici_links=4,
    vmem_bytes=128 * 1024**2,
)

# The paper's three x86 test systems, kept for microbenchmark-model fidelity
# (cycles/element conversions in benchmarks/fig2*).  Bandwidths are the
# paper's measured STREAM Triad numbers.
WOODCREST = ChipSpec("woodcrest", 2 * 4 * 3.0e9, 2 * 4 * 3.0e9, 6.5e9, 8 * 1024**3, 0.0, 0, 4 * 1024**2)
SHANGHAI = ChipSpec("shanghai", 8 * 4 * 2.4e9, 8 * 4 * 2.4e9, 20e9, 16 * 1024**3, 0.0, 0, 6 * 1024**2)
NEHALEM = ChipSpec("nehalem", 8 * 4 * 2.66e9, 8 * 4 * 2.66e9, 35e9, 24 * 1024**3, 0.0, 0, 8 * 1024**2)

CHIPS = {c.name: c for c in (TPU_V5E, WOODCREST, SHANGHAI, NEHALEM)}

#: ``jax.Device.device_kind`` -> ChipSpec: the one table that says which
#: accelerator a run is on.  A TPU kind missing here is an error, never a
#: silent default.
DEVICE_KINDS = {"TPU v5 lite": TPU_V5E, "TPU v5e": TPU_V5E}


def chip_for_device(device=None) -> ChipSpec:
    """The ChipSpec of ``device`` (default: the first JAX device).

    Raises ValueError off-TPU and for a TPU kind not in ``DEVICE_KINDS``.
    """
    import jax

    d = device if device is not None else jax.devices()[0]
    if d.platform != "tpu":
        raise ValueError(f"{d.platform} device {d.device_kind!r} is not a TPU")
    try:
        return DEVICE_KINDS[d.device_kind]
    except KeyError:
        raise ValueError(f"unknown TPU kind {d.device_kind!r}; add it to "
                         "utils.hw.DEVICE_KINDS") from None


# ---------------------------------------------------------------------------
# Pallas VMEM budget: one rule for the kernels' compiler limit and the probes
# ---------------------------------------------------------------------------

#: share of a core's VMEM a kernel may reserve; the rest stays with Mosaic's
#: own internal scratch
VMEM_BUDGET_FRACTION = 0.75
#: added to a kernel's buffer claim for its in-register temporaries' spills
VMEM_HEADROOM_BYTES = 1024**2


def vmem_limit(claim: int) -> int:
    """The ``vmem_limit_bytes`` a kernel passes to Mosaic for a working
    set of ``claim`` bytes (its double-buffered blocks + resident vectors)."""
    return int(claim) + VMEM_HEADROOM_BYTES


def vmem_budget(chip: ChipSpec) -> int:
    """The largest ``vmem_limit`` any kernel may request on ``chip``."""
    return int(chip.vmem_bytes * VMEM_BUDGET_FRACTION)


def vmem_fits(claim: int, chip: ChipSpec) -> bool:
    """The probes' test: would the kernel's limit stay inside the budget?"""
    return vmem_limit(claim) <= vmem_budget(chip)



@dataclass(frozen=True)
class RooflineTerms:
    """The three roofline times (seconds) for one program on `chips` chips."""

    compute_s: float
    memory_s: float
    collective_s: float
    chips: int
    flops: float
    bytes_hbm: float
    bytes_collective: float

    @property
    def bound(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def critical_s(self) -> float:
        """Lower-bound step time if the three resources overlap perfectly."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def serial_s(self) -> float:
        """Upper-bound step time with zero overlap."""
        return self.compute_s + self.memory_s + self.collective_s

    def mfu_bound(self, model_flops: float) -> float:
        """Max achievable MFU given the roofline (uses the critical path)."""
        if self.critical_s == 0:
            return 0.0
        achievable = model_flops / self.critical_s
        return achievable / (self.chips * TPU_V5E.peak_flops_bf16)

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["bound"] = self.bound
        d["critical_s"] = self.critical_s
        return d


def roofline(
    flops: float,
    bytes_hbm: float,
    bytes_collective: float,
    chips: int,
    chip: ChipSpec = TPU_V5E,
    collective_links: int | None = None,
) -> RooflineTerms:
    """Three-term roofline per the assignment.

    compute    = HLO_FLOPs / (chips * peak)
    memory     = HLO_bytes / (chips * HBM_bw)
    collective = collective_bytes / (chips * link_bw)

    ``flops``/``bytes`` are *global* (whole-program, all chips) quantities,
    as reported by XLA's cost_analysis on the SPMD-partitioned module times
    the device count, or summed per-device.  ``collective_links`` lets a
    caller credit multiple ICI links (e.g. a 2D-torus all-reduce uses all 4).
    """
    links = 1 if collective_links is None else collective_links
    return RooflineTerms(
        compute_s=flops / (chips * chip.peak_flops_bf16),
        memory_s=bytes_hbm / (chips * chip.hbm_bytes_per_s),
        collective_s=bytes_collective / (chips * chip.ici_bytes_per_s_per_link * links),
        chips=chips,
        flops=flops,
        bytes_hbm=bytes_hbm,
        bytes_collective=bytes_collective,
    )


def model_flops_per_token(n_params_active: float) -> float:
    """The standard 6N approximation (fwd 2N + bwd 4N) per token."""
    return 6.0 * n_params_active


def decode_flops_per_token(n_params_active: float) -> float:
    """Forward-only: 2N per generated token."""
    return 2.0 * n_params_active
