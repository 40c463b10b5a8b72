"""Hardware constants for both the paper's target (Stratix 10 / Bittware 520N)
and our target (TPU v5e), used by the analytical models and the roofline pass.

The Stratix-10 numbers come straight from the paper (Sections II, VI); the TPU
numbers are the grading constants given for this reproduction:
197 TFLOP/s bf16 per chip, 819 GB/s HBM, ~50 GB/s per ICI link.
"""

from __future__ import annotations

import dataclasses
import functools
import os

# ---------------------------------------------------------------------------
# Paper hardware: Intel Stratix 10 GX2800 on a Bittware 520N.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Stratix10:
    """Constants from the paper (Sections II-A/II-B/VI)."""

    # Four DDR4@2400MT/s modules, 19200 MB/s each (Section II-A).
    ddr_modules: int = 4
    ddr_bw_per_module: float = 19200e6  # bytes/s
    # 5760 DSPs on chip; 4713 available to kernel logic after the BSP
    # (Section VI); the paper's designs use at most 4704.
    dsp_total: int = 5760
    dsp_available: int = 4713
    dsp_used_max: int = 4704
    # A DSP in fused multiply-add configuration does 2 FLOP/cycle (eq. 5).
    flop_per_dsp_cycle: int = 2
    sp_float_bytes: int = 4

    def b_ddr_floats_per_cycle(self, f_max_hz: float) -> int:
        """Eq. (4): max sp-floats/cycle one LSU can request without stalls.

        LSUs are power-of-two sized; the byte budget per cycle that one
        memory controller can sustain halves when f_max crosses 300 MHz.
        """
        if f_max_hz <= 150e6:
            raise ValueError("paper model only covers 150 MHz < f_max <= 600 MHz")
        if f_max_hz <= 300e6:
            return 16  # 64 B/cycle
        if f_max_hz <= 600e6:
            return 8  # 32 B/cycle
        raise ValueError("f_max above 600 MHz is outside the paper's model")


STRATIX10 = Stratix10()


# ---------------------------------------------------------------------------
# Our hardware: TPU v5e (per-chip), the reproduction target.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TPUv5e:
    """A TPU-family chip description.

    Despite the historical name (the class predates the chip registry), this
    is the generic per-chip record: other registry entries are instances with
    different constants.  ``name`` is the registry key and also what the
    autotuner's cache entries are tagged with.
    """

    name: str = "tpu_v5e"
    peak_flops_bf16: float = 197e12  # FLOP/s per chip
    hbm_bw: float = 819e9  # bytes/s per chip
    ici_bw_per_link: float = 50e9  # bytes/s per link (grading constant)
    # VMEM budget we allow a single kernel instance to claim.  v5e has
    # ~128 MiB VMEM per core; we leave headroom for Mosaic's own buffers
    # and for double-buffered pipelining (which doubles input block space).
    vmem_budget_bytes: int = 64 * 1024 * 1024
    # Physical VMEM per core: the ceiling of the scoped-VMEM limit a kernel
    # is compiled with (``BlockPlan.vmem_limit_bytes``).
    vmem_capacity_bytes: int = 128 * 1024 * 1024
    # MXU native tile: 128x128 systolic array, 8-deep sublane packing for
    # bf16.  All matmul block dims should be multiples of these.
    mxu_dim: int = 128
    lane_dim: int = 128
    sublane_dim: int = 8

    def peak_flops(self, dtype: str | None = None) -> float:
        """Per-dtype peak FLOP/s: the DSP-packing analogue (DESIGN.md §10).

        Stratix 10 DSPs pack two narrow fixed-point multiplies per block in
        int mode -- the same silicon does 2x the work on narrow operands.
        The MXU analogue: int8/fp8 passes run at ~2x the bf16 peak, fp32 at
        half.  ``None``/unknown dtypes report the bf16 peak.
        """
        if dtype is None:
            return self.peak_flops_bf16
        return self.peak_flops_bf16 * PEAK_FLOPS_MULT.get(str(dtype), 1.0)

    @property
    def machine_balance_hbm(self) -> float:
        """FLOP per HBM byte needed to be compute-bound (~240 for v5e)."""
        return self.peak_flops_bf16 / self.hbm_bw

    def machine_balance(self, dtype: str | None = None) -> float:
        """Dtype-aware FLOP-per-HBM-byte balance: int8 doubles the peak, so
        a quantized matmul must also deliver ~2x the arithmetic intensity
        (which its 1-byte streams do) to stay compute-bound."""
        return self.peak_flops(dtype) / self.hbm_bw

    def machine_balance_ici(self, links: int = 1) -> float:
        """FLOP per collective byte needed for collectives to hide."""
        return self.peak_flops_bf16 / (self.ici_bw_per_link * links)


Chip = TPUv5e  # the generic alias new code should use

TPU_V5E = TPUv5e()

# A second registry entry so "tune for another target" is exercised for real:
# TPU v4 per-chip numbers (275 TFLOP/s bf16, 1228 GB/s HBM, 32 MiB VMEM/core
# -> a tighter fitter budget than v5e, so some v5e-feasible blocks fail here).
TPU_V4 = TPUv5e(
    name="tpu_v4",
    peak_flops_bf16=275e12,
    hbm_bw=1228e9,
    ici_bw_per_link=50e9,
    vmem_budget_bytes=24 * 1024 * 1024,
    vmem_capacity_bytes=32 * 1024 * 1024,
)


# ---------------------------------------------------------------------------
# Chip registry: replaces the hardcoded TPU_V5E sprinkled through the kernel
# wrappers.  ``get_chip(None)`` returns the process-wide default: the
# attached TPU on a TPU backend, which the autotuner and tests can retarget
# without threading a chip argument through every call site.
# ---------------------------------------------------------------------------

_CHIPS: dict[str, Chip] = {}
# set_default_chip() retargets a whole process explicitly (tests, tuning for
# another target); it wins over everything else.
_override: str | None = None

# device_kind as JAX reports it -> registry name.  An attached TPU whose kind
# is missing here is an error, not a default: its constants are unknown, and
# another chip's would mislabel every plan and roofline computed from them.
DEVICE_KINDS = {
    "TPU v5 lite": "tpu_v5e",
    "TPU v4": "tpu_v4",
}


def register_chip(chip: Chip) -> Chip:
    """Add (or replace) a chip in the registry; returns it for chaining."""
    _CHIPS[chip.name] = chip
    return chip


register_chip(TPU_V5E)
register_chip(TPU_V4)


def chip_names() -> tuple[str, ...]:
    return tuple(sorted(_CHIPS))


@functools.cache
def attached_chip_name() -> str | None:
    """Registry name of the attached TPU (from ``device_kind``); None when
    JAX's backend is not a TPU."""
    import jax

    if jax.default_backend() != "tpu":
        return None
    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_KINDS:
        raise KeyError(
            f"attached TPU has device_kind {kind!r}, which has no chip entry; "
            f"known kinds: {sorted(DEVICE_KINDS)}"
        )
    return DEVICE_KINDS[kind]


def _default_name() -> str:
    """The target of ``get_chip(None)``: an explicit ``set_default_chip``,
    else the attached TPU, else ``REPRO_CHIP`` (the chip a CPU run models),
    else tpu_v5e."""
    return (
        _override
        or attached_chip_name()
        or os.environ.get("REPRO_CHIP")
        or TPU_V5E.name
    )


def get_chip(name: str | Chip | None = None) -> Chip:
    """Resolve a chip by registry name; ``None`` -> the current default.

    Accepts an already-resolved Chip and passes it through, so call sites can
    take ``chip: str | Chip | None`` without case analysis.
    """
    if isinstance(name, TPUv5e):
        return name
    if name is None:
        name = _default_name()
    try:
        return _CHIPS[name]
    except KeyError:
        raise KeyError(
            f"unknown chip {name!r}; registered: {chip_names()}"
        ) from None


def set_default_chip(name: str | Chip) -> Chip:
    """Set the process-wide default target (registering it if needed)."""
    global _override
    chip = name if isinstance(name, TPUv5e) else get_chip(name)
    register_chip(chip)
    _override = chip.name
    return chip


DTYPE_BYTES = {
    "float32": 4,
    "bfloat16": 2,
    "float16": 2,
    "int8": 1,
    "fp8": 1,
    "float8_e4m3fn": 1,
    "float8_e5m2": 1,
}

# Per-dtype peak-FLOPs multipliers relative to bf16 (see Chip.peak_flops):
# narrow int/fp8 streams pack 2x the MACs per unit -- the Stratix DSP
# int-mode packing trick -- while fp32 halves the MXU rate.
PEAK_FLOPS_MULT = {
    "bfloat16": 1.0,
    "float16": 1.0,
    "float32": 0.5,
    "int8": 2.0,
    "fp8": 2.0,
    "float8_e4m3fn": 2.0,
    "float8_e5m2": 2.0,
}


def dtype_bytes(dtype) -> int:
    """Element size of a dtype name/object -- the one lookup every plan
    constructor goes through (no more hardcoded ``in_dtype_bytes=2``)."""
    name = str(dtype)
    if name in DTYPE_BYTES:
        return DTYPE_BYTES[name]
    import numpy as np

    try:
        return int(np.dtype(dtype).itemsize)
    except TypeError:
        # jax-only dtypes (bfloat16 objects etc.) stringify to known names;
        # anything else falls back to the bf16 default the old call sites
        # hardcoded.
        return 2
