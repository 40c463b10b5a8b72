"""The trace reduction, on synthetic events and on a trace recorded on a v5e."""

import gzip
import json
import os

import pytest

from bench.lib import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def test_busy_is_the_union_of_overlapping_and_nested_intervals():
    ops = [("a", 0, 10), ("b", 5, 15), ("c", 6, 7), ("d", 20, 30), ("e", 40, 45)]
    assert tr.busy_ns(ops, 0, 50) == 15 + 10 + 5
    assert tr.busy_ns(ops, 8, 25) == (15 - 8) + 5  # clipped to the window
    assert tr.gaps(ops, 0, 50) == [(15, 20), (30, 40), (45, 50)]


def test_self_time_leaves_out_nested_ops():
    ops = [
        ("%while.2 = (s32[]) while(...)", 0, 100),
        ("%systolic_mmm_16x512x2048_none.33 = bf16[16,6656] custom-call(...)", 10, 30),
        ("%systolic_mmm_16x512x2048_none.34 = bf16[16,2560] custom-call(...)", 40, 50),
        ("%fusion.9 = f32[16] fusion(...)", 42, 44),
    ]
    st = tr.self_times(ops)
    assert st["while -> (s32[])"] == pytest.approx(70e-9)
    assert st["systolic_mmm_16x512x2048_none -> bf16[16,6656]"] == pytest.approx(20e-9)
    assert st["systolic_mmm_16x512x2048_none -> bf16[16,2560]"] == pytest.approx(8e-9)
    assert st["fusion -> f32[16]"] == pytest.approx(2e-9)


def test_idle_gaps_go_to_the_innermost_open_host_span():
    t = tr.Trace(
        [tr.Device([("x", 0, 10), ("y", 30, 40), ("z", 60, 100)], [])],
        [
            ("bench.window", 0, 100),
            ("bench.tick", 0, 45),
            ("bench.sample", 12, 28),
            ("bench.generator", 52, 55),
        ],
    )
    idle = tr.idle_by_host(t, t.devices[0], 0, 100)
    # gap 10-30 (midpoint 20, inside sample inside tick); gap 40-60
    # (midpoint 50: the tick has closed, the generator not yet begun)
    assert idle == {"bench.sample": pytest.approx(20e-9), "host.unannotated": pytest.approx(20e-9)}


def test_programs_take_the_phase_whose_span_enqueued_them():
    spans = [
        ("bench.prefill_chunk", 100, 110),
        ("bench.decode", 200, 210),
        ("bench.prefill_chunk", 300, 310),
        ("bench.decode", 400, 410),
    ]
    modules = [
        # (name, start, end, host enqueue time): the device runs ahead of the
        # host's annotations, so starts alone would mislead
        ("jit__lambda(1)", 99, 180, 105),
        ("jit__lambda(2)", 199, 280, 205),
        ("jit__argmax(3)", 281, 282, 207),  # enqueued in decode, runs late
        ("jit__lambda(1)", 299, 380, 305),
        ("jit__lambda(2)", 399, 480, 405),
        ("jit__argmax(3)", 481, 482, None),  # no enqueue event: nearest start
        ("jit__lambda(5)", 312, 330, 312),  # inputs not ready: enqueued late
        ("jit__gather(6)", 90, 91, 90),  # before any phase span
    ]
    t = tr.Trace([tr.Device([], modules)], spans)
    ph = tr.module_phases(t, t.devices[0])
    assert ph == {
        "jit__lambda(1)": "bench.prefill_chunk",
        "jit__lambda(2)": "bench.decode",
        "jit__argmax(3)": "bench.decode",
        "jit__lambda(5)": "bench.prefill_chunk",
    }
    runs = tr.phase_runs(t, t.devices[0], "bench.decode", 0, 1000)
    assert [r[1] for r in runs] == [199, 281, 399, 481]


def test_record_trace_keeps_ticks_with_a_chunk_and_a_decode_step():
    from bench import record_trace

    spans = [
        ("bench.window", 0, 1000),
        ("bench.tick", 0, 100), ("bench.decode", 10, 20),
        ("bench.tick", 100, 200), ("bench.prefill_chunk", 110, 120), ("bench.decode", 130, 140),
        ("bench.tick", 200, 300), ("bench.prefill_chunk", 210, 220), ("bench.decode", 230, 240),
        ("bench.tick", 300, 400), ("bench.decode", 310, 320),
    ]
    ops = [("op", t, t + 5) for t in range(0, 400, 25)]
    mods = [("jit__lambda(1)", t, t + 20, t) for t in range(0, 400, 50)]
    t = record_trace.trim(tr.Trace([tr.Device(ops, mods)], spans), 2)
    assert [o[1] for o in t.devices[0].ops] == list(range(100, 300, 25))
    assert [m[1] for m in t.devices[0].modules] == [100, 150, 200, 250]
    assert ("bench.tick", 0, 100) not in t.spans and ("bench.window", 0, 1000) in t.spans
    with pytest.raises(RuntimeError):
        record_trace.trim(tr.Trace([tr.Device(ops, mods)], spans), 3)


def test_ops_within_runs():
    dev = tr.Device([("a", 0, 5), ("b", 10, 12), ("c", 11, 20), ("d", 25, 30)], [])
    assert [o[0] for o in tr.ops_within(dev, [("m", 9, 21)])] == ["b", "c"]


@pytest.mark.parametrize("phase,per_layer", [("bench.decode", 7), ("bench.prefill_chunk", 8)])
def test_recorded_chip_trace(phase, per_layer):
    """Two ticks of minicpm3-docqa-open (31 layers), each with a prefill
    chunk and a decode step, traced on a TPU v5e and trimmed
    (``bench/record_trace.py``): each execution of the step program runs one
    systolic kernel call per projection per layer and reads the output head
    once; the phases are found.  MLA decode runs wq_a, wq_b, wkv_a, wo, gate,
    up, down; a chunk also expands the latent cache through wkv_b."""
    path = os.path.join(HERE, "data", "trace_minicpm3-docqa-open.json.gz")
    with gzip.open(path, "rt") as f:
        t = tr.Trace.from_json(json.load(f))
    dev = t.devices[0]
    lo = min(s[1] for s in t.spans)
    hi = max(s[2] for s in t.spans)
    busy = tr.busy_ns(dev.ops, lo, hi)
    assert 0 < busy < hi - lo
    layers = 31
    runs = tr.phase_runs(t, dev, phase, lo, hi)
    first, last = dev.ops[0][1], max(o[2] for o in dev.ops)
    main = [r for r in runs if r[0].startswith("jit__lambda") and first <= r[1] and r[2] <= last]
    assert main
    ops = tr.ops_within(dev, main)
    kern = [o for o in ops if o[0].startswith("%systolic_")]
    assert len(kern) == len(main) * layers * per_layer
    assert sum("%p__lm_head" in o[0] for o in ops) >= len(main)
