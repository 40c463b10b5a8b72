"""Per-layer metrics read from the program's own spans: the scheduler's tick
and its phases, against the device's ops.

The program (``repro.obs.trace``) opens one ``serve.tick`` span per
scheduler tick, with a child span for each phase (``serve.admission``,
``serve.prefill_chunk``, ``serve.decode_tick`` and theirs,
``serve.gauges``), and sets the tick's counts as args at its end
(``decoded``, ``chunks``, ``queued``, ``prefilling``, ``kv_live_bytes``,
``kv_reserved_bytes``).  Each span goes to the tracer's ring buffer and,
while a profiler trace records, to the profile's host plane.

A program span here is ``(name, start_ns, end_ns, args)`` on the trace's
clock.  Two sources give them:

* ``load_program(log_dir)``: the host plane of a profile, beside the
  device's ops (``bench/record_program_trace.py`` keeps them in a test
  fixture);
* ``program_spans(run)``: the program's ring buffer in the running
  process, put on the trace's clock by the harness's ``bench.window`` span,
  which opened as the host's ``perf_counter`` read ``run.window.t0`` and
  closed as it read ``run.window.traced_end``.  A run reduces its profile
  and deletes it before the readers run, so this is what the benchmark's
  readers use.  Where the program opens no such span (or keeps no ring
  buffer), there is nothing to read and each reader returns None.

The host's annotations and the device's ops share the trace's clock only
roughly: on a v5e the device's timestamps run about 1.3 ms ahead of the
host's (executions appear to begin before the host enqueued them).  The
readers put the program's spans on the device's clock first
(``clock_offset``), so that the device's idle time after a step lands in
the host phase that followed the step, not in the wait for it.
"""

from __future__ import annotations

import bisect
import collections
import glob
import math
import os
import statistics

from bench.lib import trace_reduce as tr

PREFIXES = ("serve.", "engine.", "kv.")
TICK = "serve.tick"
CHUNK_PROGRAM = "jit_prefill_chunk"
OUTSIDE = "(no program span)"


def load_program(log_dir: str) -> list:
    """The program's spans on the host plane of the newest profile under
    ``log_dir``, with their args, by start."""
    from jax.profiler import ProfileData

    files = sorted(
        glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/host"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(PREFIXES):
                        out.append((e.name, e.start_ns, e.end_ns, dict(e.stats)))
    return sorted(out, key=lambda p: p[1])


def from_tracer(tracer, t0: float, t1: float, lo: float, hi: float) -> list:
    """The tracer's spans that overlap the host interval [t0, t1]
    (``perf_counter`` seconds), mapped linearly onto [lo, hi] (trace ns)."""
    origin = getattr(tracer, "t0", None)
    if origin is None or t1 <= t0:
        return []
    scale = (hi - lo) / (t1 - t0)
    out = []
    for ev in tracer.events():
        if ev.get("ph") != "X" or not ev["name"].startswith(PREFIXES):
            continue
        s = origin + ev["ts"] * 1e-6
        e = s + ev["dur"] * 1e-6
        if e < t0 or s > t1:
            continue
        out.append((ev["name"], lo + (s - t0) * scale, lo + (e - t0) * scale, ev.get("args", {})))
    return sorted(out, key=lambda p: p[1])


def program_spans(run) -> list:
    """The run's program spans in its traced window (kept on ``run``)."""
    if getattr(run, "program", None) is None:
        run.program = []
        win = run.window
        if getattr(win, "traced_end", None) is not None and run.hi > run.lo:
            from repro.obs import trace as obs_trace

            run.program = from_tracer(obs_trace.get_tracer(), win.t0, win.traced_end, run.lo, run.hi)
    return run.program


# -- the readers ----------------------------------------------------------------


def ticks(program: list, lo: float, hi: float) -> list:
    """The ``serve.tick`` spans wholly inside [lo, hi]."""
    return [p for p in program if p[0] == TICK and lo <= p[1] and p[2] <= hi]


def clock_offset(dev) -> float:
    """Nanoseconds by which the device's timestamps run ahead of the host's.

    No execution begins before the host enqueued it (``DoEnqueueProgram``,
    matched by ``run_id``).  One that found the device idle begins at once,
    so its start lies the offset (less a launch latency) before its
    enqueue; one that queued behind others begins after it and says
    nothing of the offset.  The median over the first kind; 0 where no
    execution began before its enqueue."""
    ahead = [enq - s for _, s, _, enq in dev.modules if enq is not None and enq > s]
    return float(statistics.median(ahead)) if ahead else 0.0


def _on_device_clock(spans: list, off: float) -> list:
    return [(n, s - off, e - off, a) for n, s, e, a in spans]


def _idle_in(gaps: list, starts: list, s: float, e: float) -> float:
    """Nanoseconds of ``gaps`` (sorted, disjoint) inside [s, e)."""
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    total = 0.0
    while i < len(gaps) and gaps[i][0] < e:
        total += max(0.0, min(gaps[i][1], e) - max(gaps[i][0], s))
        i += 1
    return total


def tick_idle_ms(program: list, dev, lo: float, hi: float) -> float | None:
    """Device idle time inside ``serve.tick`` spans wholly in the window,
    per tick (ms)."""
    t = ticks(program, lo, hi)
    if dev is None or not t:
        return None
    off = clock_offset(dev)
    gaps = tr.gaps(dev.ops, lo - off, hi - off)
    starts = [g[0] for g in gaps]
    idle = sum(_idle_in(gaps, starts, s, e) for _, s, e, _ in _on_device_clock(t, off))
    return idle * 1e-6 / len(t)


def prefill_tick_share(program: list, dev, lo: float, hi: float) -> float | None:
    """Device time of the ``jit_prefill_chunk`` executions that begin
    inside ``serve.tick`` spans wholly in the window, over the summed
    durations of those spans (%)."""
    t = ticks(program, lo, hi)
    if dev is None or not t:
        return None
    runs = [m for m in dev.modules if m[0].split("(", 1)[0] == CHUNK_PROGRAM]
    if not runs:
        return None
    t = _on_device_clock(t, clock_offset(dev))
    starts = [s for _, s, _, _ in t]
    spent = 0.0
    for _, s, e, *_ in runs:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < t[i][2]:
            spent += e - s
    return 100.0 * spent / sum(e - s for _, s, e, _ in t)


def kv_live_share(program: list, lo: float, hi: float) -> float | None:
    """Mean over the ticks wholly in the window of the KV bytes live over
    the KV bytes reserved, from ``serve.tick``'s args (%)."""
    shares = [
        a["kv_live_bytes"] / a["kv_reserved_bytes"]
        for _, _, _, a in ticks(program, lo, hi)
        if a.get("kv_reserved_bytes") and "kv_live_bytes" in a
    ]
    return 100.0 * sum(shares) / len(shares) if shares else None


# -- where the idle time goes ----------------------------------------------------


def _innermost(program: list) -> list:
    """[(t, name)] in time order: from ``t`` on, ``name`` is the innermost
    program span open (``OUTSIDE`` where none is).  Spans of one thread
    nest."""
    out, stack = [], []  # stack: (end, name) of the open spans

    def close(until):
        while stack and stack[-1][0] <= until:
            end = stack.pop()[0]
            out.append((end, stack[-1][1] if stack else OUTSIDE))

    for name, s, e, _ in sorted((p for p in program if p[2] > p[1]), key=lambda p: (p[1], -p[2])):
        close(s)
        out.append((s, name))
        stack.append((e, name))
    close(math.inf)
    return out


def idle_by_program(program: list, dev, lo: float, hi: float) -> dict:
    """{program span: seconds} of device idle time in the window [lo, hi)
    (host clock).  A gap is cut where the innermost open program span
    changes, and each piece goes to that span (``OUTSIDE`` where none is):
    one gap often runs through several phases, from the end of one step to
    the start of the next."""
    off = clock_offset(dev)
    labels = _innermost(_on_device_clock(program, off))
    times = [t for t, _ in labels]
    out = collections.Counter()
    for s, e in tr.gaps(dev.ops, lo - off, hi - off):
        i = bisect.bisect_right(times, s) - 1
        while s < e:
            end = min(e, times[i + 1]) if i + 1 < len(times) else e
            out[labels[i][1] if i >= 0 else OUTSIDE] += (end - s) * 1e-9
            s, i = end, i + 1
    return out
