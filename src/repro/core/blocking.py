"""TPU-side generalisation of the paper's reuse-ratio blocking (Def. 4).

The paper derives its level-1 block sizes d_i1/d_j1 from *balance equations*:
the on-chip cache must re-serve each element r = B_array / B_global times so
the slower memory level never stalls the MACs (eqs. 14, 18).  On TPU the same
argument applies three times:

  level 0  MXU tile        (128 x 128, fixed by hardware -- the paper's d_p)
  level 1  VMEM block      (bm, bn, bk)    <- this module derives these
  level 2  per-chip shard  (HBM resident)
  level 3  mesh shard      (ICI collectives -- see distributed/sharding.py)

At each level the condition is identical in shape to eq. (14):

  arithmetic_intensity(block) >= machine_balance(level)

and the paper's "fitter failure" rows of Table I become an *analytical* VMEM
capacity check here (we reject infeasible shapes before lowering instead of
after hours of place-and-route).
"""

from __future__ import annotations

import dataclasses
import math

from repro.core import hw


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """A concrete (bm, bn, bk) tiling of an (M, N, K) matmul."""

    m: int
    n: int
    k: int
    bm: int
    bn: int
    bk: int
    in_dtype_bytes: int = 2  # bf16 streams (derived from in_dtype when set)
    acc_dtype_bytes: int = 4  # fp32 accumulator, always
    double_buffer: bool = True
    # -- level-3 (mesh): degree of the "model" axis this plan shards over.
    # tp=1 is the single-chip plan; tp>1 describes the collective-matmul
    # decomposition of distributed/collective_matmul.py (A row-sharded, B
    # column-sharded, tp ring steps of an (m/tp, k) x (k, n/tp) block each).
    tp: int = 1
    # -- dtype identity: when set, ``in_dtype_bytes`` is derived from the
    # hw.DTYPE_BYTES table (so a wrong-dtype plan can't silently use bf16
    # sizing) and the roofline compute term uses the per-dtype peak
    # (int8 ~ 2x bf16, the DSP-packing analogue).
    in_dtype: str | None = None
    # -- quantization (DESIGN.md §10): scale-block length along K (0 = not
    # quantized).  Quantized plans stream fp32 scale sidecars -- per-row x
    # per-k-block for A, per-k-block x per-column for B -- which count
    # toward VMEM occupancy and HBM traffic below.
    quant_block_k: int = 0
    scale_dtype_bytes: int = 4
    # Output element size; None = same as the input stream (fp plans).
    # Quantized plans emit wide outputs (bf16/fp32) from narrow streams.
    out_dtype_bytes: int | None = None

    def __post_init__(self):
        if self.in_dtype is not None:
            object.__setattr__(
                self, "in_dtype_bytes", hw.dtype_bytes(self.in_dtype)
            )

    @property
    def _out_bytes(self) -> int:
        return (
            self.in_dtype_bytes
            if self.out_dtype_bytes is None
            else self.out_dtype_bytes
        )

    @property
    def _k_scale_blocks(self) -> int:
        """Number of scale blocks along K (0 when unquantized)."""
        if not self.quant_block_k:
            return 0
        return math.ceil(self.k / self.quant_block_k)

    # -- level-1 (VMEM) occupancy: the "fitter" check -----------------------

    def vmem_bytes(self) -> int:
        """Working set of one grid step: A block + B block + accumulator + out.

        Audited against the kernel's actual buffers (kernels/systolic/
        kernel.py): Pallas double-buffers the two *streamed* inputs (the
        paper's overlapped Read/Compute, Section V) because their block
        index advances every k step; the fp32 accumulator is single-buffered
        VMEM scratch (C-stationary); and the output window is a single
        buffer too -- its (i, j) index is constant across the whole
        k-innermost sweep and it is written exactly once, on the final k
        step.  Counting the output double-buffered (the old accounting)
        overstated the working set by bm*bn*in_bytes and made ``fits_vmem``
        reject feasible near-budget plans.  Should Mosaic revolve a second
        out buffer to overlap the (i, j) copy-out with the next block, that
        lives in the headroom ``Chip.vmem_budget_bytes`` already reserves
        below physical VMEM (see core/hw.py).
        """
        mult = 2 if self.double_buffer else 1
        a_block = self.bm * self.bk * self.in_dtype_bytes * mult
        b_block = self.bk * self.bn * self.in_dtype_bytes * mult
        acc = self.bm * self.bn * self.acc_dtype_bytes
        out = self.bm * self.bn * self._out_bytes
        scales = 0
        if self.quant_block_k:
            # One (bm, 1) A-scale and one (1, bn) B-scale column per k-step,
            # streamed (double-buffered) like the value blocks they scale.
            scales = (self.bm + self.bn) * self.scale_dtype_bytes * mult
        return a_block + b_block + acc + out + scales

    def fits_vmem(self, chip: hw.Chip | str | None = None) -> bool:
        return self.vmem_bytes() <= hw.get_chip(chip).vmem_budget_bytes

    def vmem_limit_bytes(self, chip: hw.Chip | str | None = None) -> int:
        """Scoped-VMEM limit the kernel running this plan is compiled with.

        Without one, Mosaic enforces its own default (16 MiB on v5e), far
        below the fitter's budget, and refuses every plan in between.  Mosaic
        also allocates beyond ``vmem_bytes()``: a second output window and
        the (bm, bn) fp32 result of each block dot.  Compiled against a
        described v5e, the smallest limit that compiled was at most 1.46x
        the working set over bf16 and fp32 plans from 256x256x512 to
        1024x1024x2048, so twice the working set covers it.  The floor keeps
        small plans at the compiler default; the cap is the physical VMEM.
        """
        chip = hw.get_chip(chip)
        return min(
            chip.vmem_capacity_bytes, max(2 * self.vmem_bytes(), _MIN_VMEM_LIMIT)
        )

    def mxu_aligned(self, chip: hw.Chip | str | None = None) -> bool:
        """All three dims hardware aligned (lane=128; sublane handled by
        Mosaic for the minor-most dim)."""
        chip = hw.get_chip(chip)
        return (
            self.bm % chip.sublane_dim == 0
            and self.bn % chip.lane_dim == 0
            and self.bk % chip.lane_dim == 0
        )

    # -- reuse ratios (paper eq. 14 reinterpreted) ---------------------------

    def reuse_ratios(self) -> tuple[float, float]:
        """(r_A, r_B): how many times each loaded element is used.

        With C-stationary k-innermost ordering, an A element loaded into
        VMEM is used bn times (once per output column in the block) and a
        B element bm times.  These play exactly the role of eq. (14).
        """
        return float(self.bn), float(self.bm)

    def hbm_traffic_bytes(self) -> int:
        """Total HBM bytes moved by the whole (M,N,K) matmul under this plan.

        A is re-read once per column-block (N/bn times), B once per
        row-block (M/bm times); C is written once (k-innermost keeps
        partials in VMEM; this is the adaptation of Section V where the
        FPGA instead re-streams partial sums through the k 'layers').
        """
        n_col_blocks = math.ceil(self.n / self.bn)
        n_row_blocks = math.ceil(self.m / self.bm)
        a_bytes = self.m * self.k * self.in_dtype_bytes * n_col_blocks
        b_bytes = self.k * self.n * self.in_dtype_bytes * n_row_blocks
        c_bytes = self.m * self.n * self._out_bytes
        s_bytes = 0
        if self.quant_block_k:
            kb = self._k_scale_blocks
            # Scale sidecars re-stream with their value arrays: A's (M, kb)
            # once per column block, B's (kb, N) once per row block.
            s_bytes = (
                self.m * kb * self.scale_dtype_bytes * n_col_blocks
                + kb * self.n * self.scale_dtype_bytes * n_row_blocks
            )
        return a_bytes + b_bytes + c_bytes + s_bytes

    def flops(self) -> int:
        return 2 * self.m * self.n * self.k

    def arithmetic_intensity(self) -> float:
        """FLOP per HBM byte under this plan (to compare with ~240)."""
        return self.flops() / self.hbm_traffic_bytes()

    def compute_bound(self, chip: hw.Chip | str | None = None) -> bool:
        return self.arithmetic_intensity() >= hw.get_chip(chip).machine_balance(
            self.in_dtype
        )

    # -- roofline terms (seconds on one chip) --------------------------------

    def compute_seconds(self, chip: hw.Chip | str | None = None) -> float:
        return self.flops() / hw.get_chip(chip).peak_flops(self.in_dtype)

    def memory_seconds(self, chip: hw.Chip | str | None = None) -> float:
        return self.hbm_traffic_bytes() / hw.get_chip(chip).hbm_bw

    def bound_by(self, chip: hw.Chip | str | None = None) -> str:
        return (
            "compute"
            if self.compute_seconds(chip) >= self.memory_seconds(chip)
            else "memory"
        )

    # -- level-3 (mesh) balance: eq. (14) at the ICI level -------------------
    # The overlapped collective matmul runs tp ring steps; during each, one
    # A chunk of (m/tp, k) crosses one link while an (m/tp, k) x (k, n/tp)
    # block matmul computes.  "Balanced" = the hop hides under the step, the
    # mesh-level analogue of the paper's stall-free condition.

    def shard_shape(self) -> tuple[int, int, int]:
        """The per-ring-step (m, n, k) problem each shard computes."""
        return (self.m // self.tp, self.n // self.tp, self.k)

    def hop_bytes(self) -> int:
        """Bytes one ``ppermute`` hop moves (one A chunk)."""
        if self.tp == 1:
            return 0
        return (self.m // self.tp) * self.k * self.in_dtype_bytes

    def hop_seconds(self, chip: hw.Chip | str | None = None, links: int = 1) -> float:
        return self.hop_bytes() / (hw.get_chip(chip).ici_bw_per_link * links)

    def shard_step_seconds(self, chip: hw.Chip | str | None = None) -> float:
        """Compute time of one ring step's block matmul on one shard."""
        sm, sn, sk = self.shard_shape()
        return 2 * sm * sn * sk / hw.get_chip(chip).peak_flops(self.in_dtype)

    def mesh_balanced(self, chip: hw.Chip | str | None = None, links: int = 1) -> bool:
        """Collective-bytes-under-compute: every hop hides under a step."""
        if self.tp == 1:
            return True
        return self.hop_seconds(chip, links) <= self.shard_step_seconds(chip)


# Mosaic's default scoped-VMEM limit on v5e: no plan is given less.
_MIN_VMEM_LIMIT = 16 * 1024 * 1024


def _round_to(x: int, quantum: int) -> int:
    return max(quantum, (x // quantum) * quantum)


def round_up(x: int, q: int) -> int:
    """Smallest multiple of q >= x (the padding quantum used everywhere)."""
    return (x + q - 1) // q * q


def derive_block_plan(
    m: int,
    n: int,
    k: int,
    *,
    in_dtype: str | None = None,
    in_dtype_bytes: int | None = None,
    chip: hw.Chip | str | None = None,
    max_bm: int = 1024,
    max_bn: int = 1024,
    max_bk: int = 2048,
) -> BlockPlan:
    """Derive a balanced (bm, bn, bk) from the level-1 balance equation.

    This is the paper's eq. (18) for TPU: grow the block until the reuse
    ratios satisfy the machine balance, subject to the VMEM 'fitter' check.
    Preference order mirrors the paper's observation that the contraction
    dim (their d_k0, our bk) is the cheap axis to grow -- it adds reuse for
    *neither* operand but amortises accumulator traffic and lengthens the
    pipeline (their register chains, our MXU pipeline occupancy).

    ``in_dtype`` is the preferred way to size the streams (element bytes
    from the ``hw.DTYPE_BYTES`` table); the raw ``in_dtype_bytes`` knob
    remains for callers that genuinely have no dtype, defaulting to bf16.
    """
    chip = hw.get_chip(chip)
    if in_dtype is not None:
        in_dtype_bytes = hw.dtype_bytes(in_dtype)
    elif in_dtype_bytes is None:
        in_dtype_bytes = hw.dtype_bytes("bfloat16")
    quantum = chip.lane_dim

    # Start square and balanced: need harmonic-mean(bm,bn)/2 * 2/bytes >= CB
    #   AI(large K) ~= 2*bm*bn / ((bm+bn)*bytes)  =>  bm=bn=512 gives 256 @bf16.
    target = chip.machine_balance_hbm * in_dtype_bytes  # bm==bn target value
    side = _round_to(int(2 ** math.ceil(math.log2(max(quantum, target)))), quantum)

    bm = min(side, _round_to(m, chip.sublane_dim) if m < side else side, max_bm)
    bn = min(side, _round_to(n, quantum) if n < quantum else side, max_bn)
    bm = max(bm, chip.sublane_dim)
    bn = max(bn, quantum)

    # bk: as large as VMEM allows (paper: d_k0 'controls the data throughput
    # between processing elements'); bounded by K itself.
    bk = min(max_bk, _round_to(k, quantum) if k >= quantum else quantum)
    plan = BlockPlan(m, n, k, bm, bn, bk, in_dtype=in_dtype, in_dtype_bytes=in_dtype_bytes)
    while not plan.fits_vmem(chip) and bk > quantum:
        bk //= 2
        plan = BlockPlan(m, n, k, bm, bn, bk, in_dtype=in_dtype, in_dtype_bytes=in_dtype_bytes)
    while not plan.fits_vmem(chip) and (bm > chip.sublane_dim or bn > quantum):
        if bm >= bn and bm > chip.sublane_dim:
            bm //= 2
        else:
            bn //= 2
        plan = BlockPlan(m, n, k, bm, bn, bk, in_dtype=in_dtype, in_dtype_bytes=in_dtype_bytes)
    if not plan.fits_vmem(chip):
        raise ValueError(f"no feasible block plan for ({m},{n},{k})")
    return plan


# ---------------------------------------------------------------------------
# Level-3: the same balance equation at the mesh/ICI level (beyond paper).
# ---------------------------------------------------------------------------


def tensor_parallel_balance(
    m: int,
    n: int,
    k: int,
    tp: int,
    *,
    in_dtype: str | None = None,
    in_dtype_bytes: int | None = None,
    links: int = 1,
    chip: hw.Chip | str | None = None,
) -> dict[str, float]:
    """Check eq.-(14)-style balance for a TP-sharded matmul.

    Shard N over `tp` chips; each step all-gathers the (m,k) activations
    (ring: (tp-1)/tp of the tensor crosses each link) and computes
    2*m*(n/tp)*k FLOPs.  Returns the two times and the ratio; ratio <= 1
    means the collective hides under compute (balanced), the mesh-level
    analogue of 'no stalls'.
    """
    chip = hw.get_chip(chip)
    if in_dtype is not None:
        in_dtype_bytes = hw.dtype_bytes(in_dtype)
    elif in_dtype_bytes is None:
        in_dtype_bytes = hw.dtype_bytes("bfloat16")
    per_chip_flops = 2 * m * n * k / tp
    ag_bytes = m * k * in_dtype_bytes * (tp - 1) / tp
    t_compute = per_chip_flops / chip.peak_flops(in_dtype)
    t_coll = ag_bytes / (chip.ici_bw_per_link * links)
    return {
        "t_compute": t_compute,
        "t_collective": t_coll,
        "ratio": t_coll / t_compute if t_compute else float("inf"),
        "balanced": t_coll <= t_compute,
    }
