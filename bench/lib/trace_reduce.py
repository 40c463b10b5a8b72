"""From a profiler trace to device busy time, per-phase device time and gaps.

What is read (JAX's ``.xplane.pb``, through ``jax.profiler.ProfileData``):

* device planes ``/device:TPU:<n>``: the ``XLA Modules`` line (one event per
  program execution, named ``jit_<fn>(<program id>)``) and the ``XLA Ops``
  line (one event per operation, named by its HLO text, e.g.
  ``%systolic_mmm_16x512x2048_none.33 = bf16[16,6656] custom-call(...)``;
  an op inside a loop body shows once per iteration, nested in the loop's
  own event);
* the host's ``bench.*`` annotations (``jax.profiler.TraceAnnotation``),
  which the harness opens around the calls it makes into each layer.

Host and device events share one clock in the trace, in nanoseconds.

Programs are told apart by the host span that dispatched them.  Each device
execution carries a ``run_id``, and the host's ``DoEnqueueProgram`` event of
the same ``run_id`` says when the host enqueued it; the phase
(``bench.decode``, ``bench.prefill_chunk``) is that of the latest phase span
begun before that moment.  Where no enqueue event is found, the phase span
that began nearest the execution's start stands in (device events run about
a millisecond ahead of the host's annotations in the trace).  A program takes the phase
most of its executions fall in.  The engine's jitted steps are anonymous
lambdas, so their names alone cannot say which is which.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

PHASES = ("bench.decode", "bench.prefill_chunk")


@dataclasses.dataclass
class Device:
    ops: list  # [(name, start_ns, end_ns)] sorted by start
    modules: list  # [(name, start_ns, end_ns, enqueued_ns or None)] by start


@dataclasses.dataclass
class Trace:
    devices: list  # [Device], one per chip
    spans: list  # [(name, start_ns, end_ns)] host bench.* spans, by start

    def to_json(self) -> dict:
        return {
            "devices": [{"ops": d.ops, "modules": d.modules} for d in self.devices],
            "spans": self.spans,
        }

    @classmethod
    def from_json(cls, doc: dict) -> "Trace":
        devs = [
            Device([tuple(e) for e in d["ops"]], [tuple(e) + (None,) * (4 - len(e)) for e in d["modules"]])
            for d in doc["devices"]
        ]
        return cls(devs, [tuple(e) for e in doc["spans"]])


def load_profile(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(
        glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    devices, spans, enqueued = [], [], {}
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops = sorted(
                ((e.name, e.start_ns, e.end_ns) for e in lines["XLA Ops"].events)
                if "XLA Ops" in lines
                else [],
                key=lambda x: x[1],
            )
            mods = sorted(
                ((e.name, e.start_ns, e.end_ns, dict(e.stats).get("run_id")) for e in lines["XLA Modules"].events)
                if "XLA Modules" in lines
                else [],
                key=lambda x: x[1],
            )
            devices.append((int(plane.name.rsplit(":", 1)[1]), ops, mods))
        elif plane.name.startswith("/host"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, e.start_ns, e.end_ns))
                    elif e.name == "DoEnqueueProgram":
                        rid = dict(e.stats).get("run_id")
                        if rid is not None:
                            enqueued.setdefault(rid, e.start_ns)
    devices.sort(key=lambda d: d[0])
    return Trace(
        [
            Device(ops, [(n, s, e, enqueued.get(r)) for n, s, e, r in mods])
            for _, ops, mods in devices
        ],
        sorted(spans, key=lambda s: s[1]),
    )


# -- intervals ----------------------------------------------------------------


def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) pieces of ``intervals`` clipped to [lo, hi)."""
    out = []
    for _, s, e in sorted(intervals, key=lambda x: x[1]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(intervals, lo, hi))


def gaps(intervals, lo: float, hi: float) -> list:
    """[start, end) pieces of [lo, hi) in which no interval runs."""
    out, t = [], lo
    for s, e in union(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def window(trace: Trace) -> tuple[float, float]:
    """The measured window: the harness's ``bench.window`` span."""
    for name, s, e in trace.spans:
        if name == "bench.window":
            return s, e
    raise ValueError("the trace holds no bench.window span")


# -- attribution ----------------------------------------------------------------


def module_phases(trace: Trace, dev: Device) -> dict:
    """{program name: phase} for programs dispatched inside PHASES spans."""
    starts = [s for s in trace.spans if s[0] in PHASES]
    keys = [s[1] for s in starts]
    votes = collections.defaultdict(collections.Counter)
    for name, s, _, enq in dev.modules:
        if enq is not None:
            # The latest phase span begun before the enqueue: a program whose
            # inputs were not ready is enqueued after its span has closed,
            # but before the next phase begins (the scheduler waits for each
            # phase's result before it starts the next).
            i = bisect.bisect_right(keys, enq) - 1
            if i >= 0:
                votes[name][starts[i][0]] += 1
            continue
        i = bisect.bisect_left(keys, s)
        near = [j for j in (i - 1, i) if 0 <= j < len(keys)]
        if near:
            j = min(near, key=lambda j: abs(keys[j] - s))
            votes[name][starts[j][0]] += 1
    return {name: c.most_common(1)[0][0] for name, c in votes.items()}


def phase_runs(trace: Trace, dev: Device, phase: str, lo: float, hi: float) -> list:
    """Executions [(name, start, end)] of the programs of ``phase`` in [lo, hi)."""
    progs = {n for n, p in module_phases(trace, dev).items() if p == phase}
    return [m[:3] for m in dev.modules if m[0] in progs and lo <= m[1] < hi]


def ops_within(dev: Device, runs: list) -> list:
    """Ops [(name, start, end)] that began inside one of ``runs``."""
    keys = [o[1] for o in dev.ops]
    out = []
    for _, s, e in runs:
        i = bisect.bisect_left(keys, s)
        while i < len(dev.ops) and dev.ops[i][1] < e:
            out.append(dev.ops[i])
            i += 1
    return out


# -- what the breakdown shows ----------------------------------------------------


def op_label(name: str) -> str:
    """Short stable label of an op: its HLO instruction name without the
    numeric suffix, and its result type (``convert -> bf16[21,2560,6400]``)."""
    head, _, rest = name.partition(" = ")
    inst = re.sub(r"(\.\d+)+$", "", head.lstrip("%"))
    result = rest.split(" ", 1)[0] if rest else ""
    result = re.sub(r"\{[^}]*\}", "", result)
    return f"{inst} -> {result}"[:120] if result else inst[:120]


def self_times(ops: list) -> dict:
    """{label: seconds} of each op's own time, less the ops nested in it."""
    out = collections.Counter()
    stack: list = []  # [label, end, child time]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            lbl, end, child, dur = stack.pop()
            out[lbl] += (dur - child) * 1e-9
        if stack:
            stack[-1][2] += e - s
        stack.append([op_label(name), e, 0.0, e - s])
    for lbl, _, child, dur in stack:
        out[lbl] += (dur - child) * 1e-9
    return out


def idle_by_host(trace: Trace, dev: Device, lo: float, hi: float) -> dict:
    """{host span: seconds} of device idle time, each gap put to the
    innermost bench span open at its midpoint (``host.unannotated``
    where none is)."""
    out = collections.Counter()
    spans = [s for s in trace.spans if s[0] != "bench.window"]
    starts = [s[1] for s in spans]
    for s, e in gaps(dev.ops, lo, hi):
        mid = (s + e) / 2
        # Spans nest, so the innermost open one is the latest-starting span
        # that has not ended yet.
        i = bisect.bisect_right(starts, mid) - 1
        while i >= 0 and spans[i][2] < mid:
            i -= 1
        out[spans[i][0] if i >= 0 else "host.unannotated"] += (e - s) * 1e-9
    return out


def top(counter: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(counter.items(), key=lambda kv: -kv[1])[:n]]
