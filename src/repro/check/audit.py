"""Contract auditor: BlockPlan claims vs the program jax actually traces.

The paper's design flow trusts a *static resource model* (DSP/M20K counts per
candidate geometry) to predict what the fitter will accept; ours trusts
BlockPlan's VMEM/HBM accounting to predict what Mosaic will allocate.  Both
are only as good as their agreement with the real artifact.  This module
closes the loop mechanically: every kernel dispatch path is traced abstractly
(``jax.make_jaxpr`` -- no compilation, no device, milliseconds per trace),
the ``pallas_call`` equations are pulled out of the jaxpr, and the plan's
claims are checked against the traced program:

* declared ``vmem_bytes()`` covers the actual BlockSpec window allocations,
  with the double-buffering rule applied per operand (a window is
  double-buffered iff its index map advances with the innermost grid axis --
  exactly the condition Pallas revolves buffers on);
* the kernel geometry is the one the plan declared (after the dispatcher's
  documented clamps), grids divide the padded problem, block windows divide
  their operands;
* a quantized plan's ``bk`` never straddles a ``quant_block_k`` boundary;
* ``in_dtype``/``out_dtype_bytes`` agree with ``hw.dtype_bytes`` and with the
  traced operand dtypes (no hardcoded byte widths);
* the scale sidecars are counted: the kernel's CostEstimate.bytes_accessed
  must equal ``plan.hbm_traffic_bytes()`` exactly on dividing problems.

Findings use pseudo-paths (``<plan:512x512x512/128x128x128@int8>``) so the
baseline mechanism treats them like lint findings.
"""

from __future__ import annotations

import dataclasses
import json
import math
from typing import Any, Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.check.findings import AUDIT, Finding
from repro.core import dse, hw
from repro.core.blocking import BlockPlan, round_up

# The paper-config sweep (mirrors benchmarks/tune_report.py): the square
# baseline, a skinny-M activation GEMM, and a deep-K contraction, audited at
# the fp baseline and both quantized storage dtypes.
PAPER_PROBLEMS = ((512, 512, 512), (256, 2048, 512), (512, 512, 2048))
PAPER_DTYPES = ("bfloat16", "int8", "float8_e4m3fn")


# ---------------------------------------------------------------------------
# jaxpr -> TracedKernel extraction.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TracedWindow:
    """One BlockSpec window of a traced pallas_call."""

    block_shape: tuple[int, ...]
    dtype_bytes: int
    is_output: bool
    streamed: bool  # index map advances with the innermost grid axis
    operand_shape: tuple[int, ...] | None  # aval dims (inputs only)

    @property
    def bytes(self) -> int:
        return math.prod(self.block_shape) * self.dtype_bytes

    @property
    def buffered_bytes(self) -> int:
        return self.bytes * (2 if self.streamed else 1)


@dataclasses.dataclass(frozen=True)
class TracedKernel:
    """One pallas_call equation lifted out of a jaxpr."""

    name: str
    grid: tuple[int, ...]
    windows: tuple[TracedWindow, ...]
    scratch_bytes: int
    cost_flops: int | None
    cost_bytes: int | None

    @property
    def inputs(self) -> tuple[TracedWindow, ...]:
        return tuple(w for w in self.windows if not w.is_output)

    @property
    def outputs(self) -> tuple[TracedWindow, ...]:
        return tuple(w for w in self.windows if w.is_output)

    def vmem_bytes(self) -> int:
        """The traced working set under the double-buffering rule."""
        return sum(w.buffered_bytes for w in self.windows) + self.scratch_bytes

    def block_dims(self) -> tuple[int, ...]:
        """(bm, bn, bk) recovered from a matmul call's A and O windows."""
        a, o = self.inputs[0].block_shape, self.outputs[0].block_shape
        return (a[0], o[1], a[1])


def _find_pallas_eqns(jaxpr) -> list:
    """All pallas_call equations in a jaxpr, recursing through sub-jaxprs
    (jit/closed_call/scan/cond params carry nested Jaxprs)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else [v]
            for x in vs:
                if hasattr(x, "eqns"):
                    out.extend(_find_pallas_eqns(x))
                elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                    out.extend(_find_pallas_eqns(x.jaxpr))
    return out


def _index_at(block_mapping, idx: Sequence[int]) -> tuple:
    imj = block_mapping.index_map_jaxpr
    return tuple(jax.core.eval_jaxpr(imj.jaxpr, imj.consts, *idx))


def _is_streamed(block_mapping, grid_rank: int) -> bool:
    """Does this window's index map advance with the innermost grid axis?

    Pallas revolves (double-buffers) a window to overlap its copy-in with
    compute exactly when consecutive grid steps address different blocks;
    with the k-innermost grids used here that is a function of the last grid
    index alone, so two probe points suffice.  Index maps are pure integer
    arithmetic -- evaluating them abstractly is exact.
    """
    if grid_rank == 0:
        return False
    base = [0] * grid_rank
    step = list(base)
    step[-1] = 1
    try:
        return _index_at(block_mapping, base) != _index_at(block_mapping, step)
    except Exception:
        return True  # unknown index map: assume streamed (conservative)


def _block_dim(d) -> int:
    """One block-shape entry as an int: Pallas records ``Blocked(n)`` (and
    other sized entries) for a window dim and ``Squeezed`` for a dim the
    kernel never sees, which spans one element."""
    size = getattr(d, "block_size", d)
    return 1 if size is None or isinstance(size, pl.Squeezed) else int(size)


def _eqn_to_kernel(eqn) -> TracedKernel:
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    n_in, n_out = gm.num_inputs, gm.num_outputs
    mappings = list(gm.block_mappings)
    windows = []
    # Operand avals: the eqn's invars line up with the input block mappings.
    in_avals = [getattr(v, "aval", None) for v in eqn.invars][-n_in:] if n_in else []
    for pos, bm in enumerate(mappings):
        is_output = pos >= n_in
        shape = tuple(_block_dim(d) for d in bm.block_shape)
        dtype = bm.block_aval.dtype
        aval = None if is_output else in_avals[pos]
        windows.append(
            TracedWindow(
                block_shape=shape,
                dtype_bytes=int(jnp.dtype(dtype).itemsize),
                is_output=is_output,
                streamed=_is_streamed(bm, len(grid)),
                operand_shape=(
                    tuple(int(d) for d in aval.shape)
                    if aval is not None and hasattr(aval, "shape")
                    else None
                ),
            )
        )
    # Scratch refs: inner-jaxpr invars beyond inputs+outputs.
    scratch_bytes = 0
    inner = eqn.params.get("jaxpr")
    if inner is not None:
        n_io = n_in + n_out
        for var in inner.invars[n_io:]:
            aval = var.aval
            if hasattr(aval, "shape") and hasattr(aval, "dtype"):
                scratch_bytes += math.prod(aval.shape) * jnp.dtype(
                    aval.dtype
                ).itemsize
    cost = eqn.params.get("cost_estimate")
    return TracedKernel(
        name=eqn.params.get("name") or "pallas_call",
        grid=grid,
        windows=tuple(windows),
        scratch_bytes=scratch_bytes,
        cost_flops=None if cost is None else int(cost.flops),
        cost_bytes=None if cost is None else int(cost.bytes_accessed),
    )


def trace_kernels(fn: Callable, *avals) -> list[TracedKernel]:
    """Abstractly trace ``fn(*avals)`` and lift out every pallas_call."""
    jx = jax.make_jaxpr(fn)(*avals)
    return [_eqn_to_kernel(e) for e in _find_pallas_eqns(jx.jaxpr)]


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


# ---------------------------------------------------------------------------
# Plan audit: trace the real dispatcher with an explicit plan and verify.
# ---------------------------------------------------------------------------


def _plan_path(plan: BlockPlan, dtype: str) -> str:
    return (
        f"<plan:{plan.m}x{plan.n}x{plan.k}/"
        f"{plan.bm}x{plan.bn}x{plan.bk}@{dtype}>"
    )


def _expected_blocks(plan: BlockPlan, chip: hw.Chip, quant: bool) -> tuple:
    """The geometry the dispatcher documents it will run for this plan:
    blocks clamped to the padded problem, then (quant only) bk gcd-clamped
    inside the scale block."""
    bm = min(plan.bm, round_up(plan.m, chip.sublane_dim))
    bn = min(plan.bn, round_up(plan.n, chip.lane_dim))
    bk = min(plan.bk, round_up(plan.k, chip.lane_dim))
    if quant and plan.quant_block_k:
        bk = math.gcd(bk, plan.quant_block_k)
    return bm, bn, bk


def audit_matmul_plan(
    plan: BlockPlan,
    *,
    dtype: str | None = None,
    chip: hw.Chip | str | None = None,
    declared_vmem_bytes: int | None = None,
    declared_in_dtype_bytes: int | None = None,
) -> list[Finding]:
    """Audit one BlockPlan against the traced systolic dispatch.

    ``declared_*`` override what the plan object would claim -- the
    injection point for corrupted-record tests and the ``--plans`` CLI gate
    (a DSERecord's ``vmem_kib`` is a stored copy of ``vmem_bytes()`` and can
    drift from the code that computes it).
    """
    from repro.obs import metrics
    from repro.kernels.systolic import ops as systolic_ops

    chip = hw.get_chip(chip)
    dtype = dtype or plan.in_dtype or "bfloat16"
    quant = bool(plan.quant_block_k)
    path = _plan_path(plan, dtype)
    findings: list[Finding] = []

    def emit(rule: str, message: str) -> None:
        findings.append(
            Finding(
                engine=AUDIT,
                rule=rule,
                path=path,
                line=0,
                symbol="audit_matmul_plan",
                message=message,
            )
        )

    # -- static contract checks (no trace needed) ---------------------------
    in_bytes = (
        declared_in_dtype_bytes
        if declared_in_dtype_bytes is not None
        else plan.in_dtype_bytes
    )
    table_bytes = hw.dtype_bytes(dtype)
    if in_bytes != table_bytes:
        emit(
            "dtype-bytes-mismatch",
            f"plan claims in_dtype_bytes={in_bytes} but hw.dtype_bytes"
            f"({dtype!r})={table_bytes} -- hardcoded byte width?",
        )
    if quant and plan.quant_block_k % plan.bk:
        emit(
            "scale-straddle",
            f"bk={plan.bk} straddles quant_block_k={plan.quant_block_k} "
            f"(one k-step must sit inside one scale block); the dispatcher "
            f"will gcd-clamp to bk={math.gcd(plan.bk, plan.quant_block_k)}, "
            f"so this geometry never runs as declared",
        )

    # -- trace the real dispatcher with this plan ---------------------------
    m, n, k = plan.m, plan.n, plan.k
    with metrics.disabled():
        if quant:
            qdtype = dtype
            qbk = plan.quant_block_k

            def dispatch(a, b):
                return systolic_ops.quant_matmul(
                    a, b, qdtype=qdtype, block_k=qbk, plan=plan, interpret=True
                )

            kernels = trace_kernels(
                dispatch, _sds((m, k), "float32"), _sds((k, n), "float32")
            )
        else:

            def dispatch(a, b):
                return systolic_ops.matmul(a, b, plan=plan, interpret=True)

            kernels = trace_kernels(
                dispatch, _sds((m, k), dtype), _sds((k, n), dtype)
            )
    matmuls = [kk for kk in kernels if "mmm" in kk.name or "qmm" in kk.name]
    if not matmuls:
        emit(
            "no-kernel-traced",
            "dispatcher trace contains no systolic pallas_call -- the "
            "dispatch path has changed; auditor needs updating",
        )
        return findings
    kern = matmuls[-1]

    # Geometry: the kernel must run the declared blocks modulo documented
    # clamps (problem-clamp + quant gcd-clamp).
    expected = _expected_blocks(plan, chip, quant)
    actual = kern.block_dims()
    if actual != expected:
        emit(
            "geometry-drift",
            f"plan declares blocks {(plan.bm, plan.bn, plan.bk)} (expected "
            f"{expected} after documented clamps) but the kernel traced "
            f"{actual}",
        )
    # Grid divisibility: grid x block covers the padded problem exactly.
    bm_t, bn_t, bk_t = actual
    mp, np_, kp = round_up(m, bm_t), round_up(n, bn_t), round_up(k, bk_t)
    if kern.grid[:3] != (mp // bm_t, np_ // bn_t, kp // bk_t):
        emit(
            "grid-mismatch",
            f"traced grid {kern.grid} does not tile the padded problem "
            f"({mp},{np_},{kp}) with blocks {actual}",
        )
    for w in kern.inputs:
        if w.operand_shape and any(
            od % bd for od, bd in zip(w.operand_shape, w.block_shape)
        ):
            emit(
                "window-divisibility",
                f"block window {w.block_shape} does not divide its padded "
                f"operand {w.operand_shape}",
            )

    # Traced operand dtypes vs the plan's byte claims.
    a_traced = kern.inputs[0]
    if a_traced.dtype_bytes != table_bytes:
        emit(
            "traced-dtype-mismatch",
            f"traced A-operand element size {a_traced.dtype_bytes}B != "
            f"hw.dtype_bytes({dtype!r})={table_bytes}B",
        )
    out_traced = kern.outputs[0]
    if out_traced.dtype_bytes != plan._out_bytes:
        emit(
            "out-dtype-mismatch",
            f"plan claims out_dtype_bytes={plan._out_bytes} but the kernel "
            f"writes {out_traced.dtype_bytes}B elements",
        )
    if quant:
        scale_windows = [
            w for w in kern.inputs if 1 in w.block_shape and w.dtype_bytes == 4
        ]
        if len(scale_windows) < 2:
            emit(
                "scale-sidecar-missing",
                "quantized kernel trace has no (bm,1)/(1,bn) fp32 scale "
                "windows -- sidecars not streamed?",
            )

    # VMEM coverage: the declared working set must cover the traced one
    # (windows under the streamed/double-buffer rule + scratch).  Only
    # meaningful when the kernel runs the declared geometry.
    if actual == (plan.bm, plan.bn, plan.bk):
        declared = (
            declared_vmem_bytes
            if declared_vmem_bytes is not None
            else plan.vmem_bytes()
        )
        traced = kern.vmem_bytes()
        if declared < traced:
            emit(
                "vmem-underdeclared",
                f"plan declares vmem_bytes={declared} but the traced "
                f"working set is {traced} (windows "
                f"{[ (w.block_shape, w.dtype_bytes, w.streamed) for w in kern.windows ]}"
                f" + scratch {kern.scratch_bytes}B) -- the fitter would "
                f"admit a shape that does not fit",
            )
        # HBM claim: CostEstimate must equal the plan's traffic model
        # exactly on dividing problems (both count the same re-streams).
        divides = (m % bm_t == 0 and n % bn_t == 0 and k % bk_t == 0) and (
            not quant or k % plan.quant_block_k == 0
        )
        if (
            divides
            and declared_vmem_bytes is None
            and kern.cost_bytes is not None
            and kern.cost_bytes != plan.hbm_traffic_bytes()
        ):
            emit(
                "hbm-mismatch",
                f"kernel CostEstimate.bytes_accessed={kern.cost_bytes} != "
                f"plan.hbm_traffic_bytes()={plan.hbm_traffic_bytes()} -- "
                f"traffic model and kernel disagree (scale sidecars?)",
            )
        if kern.cost_flops is not None and kern.cost_flops != 2 * mp * np_ * kp:
            emit(
                "flops-mismatch",
                f"kernel CostEstimate.flops={kern.cost_flops} != "
                f"2*M*N*K={2 * mp * np_ * kp} for the padded problem",
            )
    return findings


# ---------------------------------------------------------------------------
# DSERecord audit: stored claims vs recomputed model.
# ---------------------------------------------------------------------------


def _record_plan(record: dse.DSERecord) -> BlockPlan:
    """The BlockPlan a DSERecord describes (per-shard problem for tp > 1)."""
    sm = record.m // record.tp if record.tp else record.m
    sn = record.n // record.tp if record.tp else record.n
    return BlockPlan(
        sm,
        sn,
        record.k,
        record.bm,
        record.bn,
        record.bk,
        in_dtype=record.in_dtype,
        in_dtype_bytes=record.in_dtype_bytes,
        quant_block_k=record.quant_block_k,
        out_dtype_bytes=hw.dtype_bytes("bfloat16") if record.quant_block_k else None,
    )


def audit_record(
    record: dse.DSERecord, chip: hw.Chip | str | None = None
) -> list[Finding]:
    """Check a stored DSERecord's claims against the recomputed model.

    Records are serialized into the tune cache and survive refactors of the
    accounting they snapshot -- exactly the drift the paper's fitter had no
    defense against.
    """
    chip = hw.get_chip(chip)
    plan = _record_plan(record)
    path = f"<record:{record.m}x{record.n}x{record.k}/{record.ident}@{record.in_dtype or 'bf16'}>"
    findings: list[Finding] = []

    def emit(rule: str, message: str) -> None:
        findings.append(
            Finding(
                engine=AUDIT,
                rule=rule,
                path=path,
                line=0,
                symbol="audit_record",
                message=message,
            )
        )

    true_kib = plan.vmem_bytes() / 1024
    if not math.isclose(record.vmem_kib, true_kib, rel_tol=1e-9, abs_tol=1e-6):
        emit(
            "record-vmem-drift",
            f"record claims vmem_kib={record.vmem_kib:.3f} but the plan "
            f"computes {true_kib:.3f} KiB -- stored claim drifted from "
            f"BlockPlan.vmem_bytes()",
        )
    true_fits = plan.fits_vmem(chip) and plan.mxu_aligned(chip)
    if record.fits != true_fits:
        emit(
            "record-fits-drift",
            f"record claims fits={record.fits} but the fitter computes "
            f"{true_fits} for blocks {record.ident}",
        )
    if record.in_dtype is not None:
        table = hw.dtype_bytes(record.in_dtype)
        if record.in_dtype_bytes != table:
            emit(
                "record-dtype-bytes",
                f"record claims in_dtype_bytes={record.in_dtype_bytes} but "
                f"hw.dtype_bytes({record.in_dtype!r})={table}",
            )
    if record.quant_block_k and record.quant_block_k % record.bk:
        emit(
            "record-scale-straddle",
            f"record bk={record.bk} straddles quant_block_k="
            f"{record.quant_block_k}; dse.explore should never emit this "
            f"geometry (the kernel would run a gcd-clamped bk instead)",
        )
    return findings


# ---------------------------------------------------------------------------
# Paper-config sweep: every candidate the tuner would measure, audited.
# ---------------------------------------------------------------------------


def sweep_paper_candidates(
    chip: hw.Chip | str | None = None,
    *,
    problems: Iterable[tuple[int, int, int]] = PAPER_PROBLEMS,
    dtypes: Iterable[str] = PAPER_DTYPES,
    trace: bool = True,
    top_k: int | None = 8,
) -> tuple[list[Finding], dict[str, Any]]:
    """Audit 100% of ``tune.candidates.generate`` output for the paper config.

    Each candidate gets the record audit (stored claims) and, with
    ``trace=True``, the full traced-plan audit through the real dispatcher.
    Returns (findings, stats).
    """
    from repro.tune import candidates as tune_candidates

    chip = hw.get_chip(chip)
    findings: list[Finding] = []
    audited = 0
    traced = 0
    for m, n, k in problems:
        for dtype in dtypes:
            cands = tune_candidates.generate(
                m, n, k, dtype=dtype, chip=chip, top_k=top_k
            )
            for cand in cands:
                audited += 1
                findings.extend(audit_record(cand.record, chip))
                if trace:
                    traced += 1
                    findings.extend(
                        audit_matmul_plan(
                            _record_plan(cand.record), dtype=dtype, chip=chip
                        )
                    )
    stats = {
        "plans_audited": audited,
        "plans_traced": traced,
        "problems": list(problems),
        "dtypes": list(dtypes),
    }
    return findings, stats


# ---------------------------------------------------------------------------
# Dispatch-path structural audit: every kernel family fits and tiles.
# ---------------------------------------------------------------------------


def audit_dispatch_paths(
    chip: hw.Chip | str | None = None,
) -> tuple[list[Finding], dict[str, Any]]:
    """Trace one representative call per kernel family and sanity-check it.

    For each traced pallas_call: the buffered working set (double-buffering
    rule applied) must fit the chip's VMEM budget, and every input window
    must divide its padded operand.  The collective path needs a mesh; it is
    traced over whatever devices exist (tp=1 on a single-device CPU host --
    the ring degenerates but the dispatch path is exercised).
    """
    from repro.obs import metrics

    chip = hw.get_chip(chip)
    findings: list[Finding] = []
    stats: dict[str, Any] = {"paths": {}}

    def emit(rule: str, path: str, message: str) -> None:
        findings.append(
            Finding(
                engine=AUDIT,
                rule=rule,
                path=path,
                line=0,
                symbol="audit_dispatch_paths",
                message=message,
            )
        )

    def check(path_name: str, kernels: list[TracedKernel]) -> None:
        stats["paths"][path_name] = len(kernels)
        if not kernels:
            emit(
                "no-kernel-traced",
                f"<dispatch:{path_name}>",
                "no pallas_call in the traced dispatch path",
            )
        for kern in kernels:
            if kern.vmem_bytes() > chip.vmem_budget_bytes:
                emit(
                    "vmem-budget",
                    f"<dispatch:{path_name}>",
                    f"kernel {kern.name} working set {kern.vmem_bytes()}B "
                    f"exceeds the {chip.vmem_budget_bytes}B VMEM budget",
                )
            for w in kern.inputs:
                if w.operand_shape and any(
                    od % bd for od, bd in zip(w.operand_shape, w.block_shape)
                ):
                    emit(
                        "window-divisibility",
                        f"<dispatch:{path_name}>",
                        f"kernel {kern.name}: window {w.block_shape} does "
                        f"not divide operand {w.operand_shape}",
                    )

    with metrics.disabled():
        from repro.kernels.systolic import ops as systolic_ops

        check(
            "systolic",
            trace_kernels(
                lambda a, b: systolic_ops.matmul(a, b, interpret=True),
                _sds((512, 512), "bfloat16"),
                _sds((512, 512), "bfloat16"),
            ),
        )
        check(
            "quant",
            trace_kernels(
                lambda a, b: systolic_ops.quant_matmul(
                    a, b, qdtype="int8", interpret=True
                ),
                _sds((512, 512), "float32"),
                _sds((512, 512), "float32"),
            ),
        )
        from repro.kernels.grouped import ops as grouped_ops

        check(
            "grouped",
            trace_kernels(
                lambda x, w: grouped_ops.grouped_matmul(x, w, interpret=True),
                _sds((4, 256, 512), "bfloat16"),
                _sds((4, 512, 512), "bfloat16"),
            ),
        )
        from repro.kernels.attention import ops as attention_ops

        check(
            "attention",
            trace_kernels(
                lambda q, k, v: attention_ops.flash_attention(
                    q, k, v, interpret=True
                ),
                _sds((1, 2, 512, 128), "bfloat16"),
                _sds((1, 2, 512, 128), "bfloat16"),
                _sds((1, 2, 512, 128), "bfloat16"),
            ),
        )
        try:
            import numpy as np
            from jax.sharding import Mesh

            from repro.distributed import collective_matmul as cm

            devs = np.array(jax.devices()[:1])
            mesh = Mesh(devs, ("model",))
            check(
                "collective_matmul",
                trace_kernels(
                    lambda a, b: cm.all_gather_matmul(
                        a, b, mesh=mesh, interpret=True
                    ),
                    _sds((512, 512), "bfloat16"),
                    _sds((512, 512), "bfloat16"),
                ),
            )
        except Exception as e:  # mesh-less hosts: record the skip, no finding
            stats["paths"]["collective_matmul"] = f"skipped: {e}"
    return findings, stats


# ---------------------------------------------------------------------------
# Injected-plan specs: the CLI/CI corruption gate.
# ---------------------------------------------------------------------------


def audit_plan_spec(spec: dict, chip: hw.Chip | str | None = None) -> list[Finding]:
    """Audit one JSON plan spec (the ``--plans`` injection format).

    Required keys: m n k bm bn bk.  Optional: dtype (default bfloat16),
    quant_block_k, declared_vmem_bytes, declared_in_dtype_bytes,
    out_dtype_bytes -- the ``declared_*`` keys assert *claims* that are
    audited against the traced kernel instead of the plan's own accounting.
    """
    dtype = spec.get("dtype", "bfloat16")
    qbk = int(spec.get("quant_block_k", 0) or 0)
    plan = BlockPlan(
        int(spec["m"]),
        int(spec["n"]),
        int(spec["k"]),
        int(spec["bm"]),
        int(spec["bn"]),
        int(spec["bk"]),
        in_dtype=dtype,
        quant_block_k=qbk,
        out_dtype_bytes=(
            int(spec["out_dtype_bytes"])
            if spec.get("out_dtype_bytes") is not None
            else (hw.dtype_bytes("bfloat16") if qbk else None)
        ),
    )
    return audit_matmul_plan(
        plan,
        dtype=dtype,
        chip=chip,
        declared_vmem_bytes=spec.get("declared_vmem_bytes"),
        declared_in_dtype_bytes=spec.get("declared_in_dtype_bytes"),
    )


def load_plan_specs(path: str) -> list[dict]:
    with open(path) as f:
        doc = json.load(f)
    return doc["plans"] if isinstance(doc, dict) else doc


def run_audit(
    *,
    chip: hw.Chip | str | None = None,
    plans_file: str | None = None,
    sweep: bool = True,
    dispatch: bool = True,
) -> tuple[list[Finding], dict[str, Any]]:
    """The CLI's audit engine: dispatch paths + paper sweep + injected plans."""
    findings: list[Finding] = []
    stats: dict[str, Any] = {}
    if dispatch:
        f, s = audit_dispatch_paths(chip)
        findings.extend(f)
        stats.update(s)
    if sweep:
        f, s = sweep_paper_candidates(chip)
        findings.extend(f)
        stats.update(s)
    if plans_file:
        specs = load_plan_specs(plans_file)
        for spec in specs:
            findings.extend(audit_plan_spec(spec, chip))
        stats["injected_plans"] = len(specs)
    return findings, stats
