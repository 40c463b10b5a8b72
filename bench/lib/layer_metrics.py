"""The arithmetic behind the per-layer metrics (``bench/metrics/<name>.py``).

Each reader gets ``run``: the cell's configuration (``cfg``), mix, family,
the harness's window record (``window``: requests and ticks, host clock), and
for a traced run the reduced trace (``trace``, ``dev``, the window ``lo``/``hi``
in trace nanoseconds) and the chip's peaks.  A reader returns None where it
finds nothing to read; a share is never reported as 0 for want of data.
"""

from __future__ import annotations

import numpy as np

from bench.lib import trace_reduce as tr
from bench.lib import work

SPAN = {"decode": "bench.decode", "prefill": "bench.prefill_chunk"}


def slot_occupancy(run) -> float | None:
    """Mean share of the slots that decoded, over the ticks that decoded (%)."""
    dec = [len(t.decoded) for t in run.window.traced_ticks() if t.decoded]
    return 100.0 * float(np.mean(dec)) / run.slots if dec else None


def queue_wait_p95_ms(run) -> float | None:
    """95th percentile of due -> admitted to a slot, over the whole window
    (ms, host clock)."""
    w = [r.admitted - r.due for r in run.window.recs if r.admitted is not None and r.admitted >= run.window.t0]
    return float(np.quantile(w, 0.95)) * 1e3 if w else None


def _calls(run, phase: str) -> list:
    """What the harness counted in the traced part of the window: (rows,
    live, head_rows) per decode step or per prefill chunk, in the model's
    terms (live rows, the head only where it yields a served token)."""
    ticks = run.window.traced_ticks()
    if phase == "decode":
        return [(len(t.decoded), 0, len(t.decoded)) for t in ticks if t.decoded]
    return [(n, off + n, 1 if last else 0) for t in ticks for off, n, last in t.chunks]


def _steps(run, phase: str) -> list:
    """[(ops of one execution)] of the phase's step programs (those that run
    the systolic kernel), for each execution wholly inside the window."""
    runs = tr.phase_runs(run.trace, run.dev, SPAN[phase], run.lo, run.hi)
    out = []
    for r in runs:
        if r[2] > run.hi:
            continue
        ops = tr.ops_within(run.dev, [r])
        if any(o[0].startswith("%systolic_") for o in ops):
            out.append(ops)
    return out


def phase_ms(run, phase: str) -> float | None:
    """Device time of one execution of the phase's step program (ms): the
    union of its ops' intervals, averaged over the executions traced."""
    if run.dev is None:
        return None
    steps = _steps(run, phase)
    if not steps:
        return None
    return sum(tr.busy_ns(ops, run.lo, run.hi) for ops in steps) * 1e-6 / len(steps)


def gemm_roofline(run, phase: str) -> float | None:
    """Least time of the phase's weight GEMMs (every projection and the
    output head, at the shapes the model needs, bf16) over the device time
    of the ops that computed them: the systolic kernel's custom calls and
    the ops that read the output head's weight (%).

    The least time per step is the mean over the steps or chunks the harness
    counted; the device time, that of the traced executions.  Silent unless
    every execution holds exactly one kernel call per projection per layer
    and reads the head: a GEMM moved to another implementation, or a changed
    count, would otherwise be timed against the wrong work."""
    if run.dev is None or run.peaks is None:
        return None
    calls = _calls(run, phase)
    steps = _steps(run, phase)
    per_step = len(run.fam.layer_gemms(run.cfg, phase, 1, 1)) * run.cfg["num_hidden_layers"]
    spent = 0.0
    for ops in steps:
        kern = [o for o in ops if o[0].startswith("%systolic_")]
        head = [o for o in ops if "%p__lm_head" in o[0]]
        if len(kern) != per_step or not head:
            return None
        spent += sum(e - s for _, s, e in kern + head) * 1e-9
    if not calls or not steps:
        return None
    nbytes = work.DTYPE_BYTES[run.cfg["compute_dtype"]]
    least = 0.0
    for rows, live, head_rows in calls:
        for m, n, k in work.step_gemms(run.fam, run.cfg, phase, rows, live, head_rows):
            if m:
                least += work.least_seconds(m, n, k, run.peaks, nbytes)
    return 100.0 * (least / len(calls)) * len(steps) / spent


def idle_share(run) -> float | None:
    """Share of the traced window in which no op ran on the device (%)."""
    if run.dev is None or run.hi <= run.lo:
        return None
    busy = np.mean([tr.busy_ns(d.ops, run.lo, run.hi) for d in run.trace.devices])
    return 100.0 * (1.0 - busy / (run.hi - run.lo))


def mfu(run) -> float | None:
    """Model FLOPs of the tokens prefilled and decoded in the traced part of
    the window over its seconds times the chip's bf16 peak (%)."""
    ticks = run.window.traced_ticks()
    if run.peaks is None or not ticks:
        return None
    flops = 0.0
    for t in ticks:
        flops += sum(work.decode_flops(run.fam, run.cfg, ctx) for ctx in t.decoded)
        flops += sum(work.chunk_flops(run.fam, run.cfg, off, n, last) for off, n, last in t.chunks)
    seconds = ticks[-1].end - run.window.t0
    return 100.0 * flops / (seconds * run.peaks["bf16_flops"])
