"""The precision control fails ``correct`` where the program passes.

The control is the program with its own int8 path switched on
(``bench/calibrate.py``: int8 weights and activations in every projection),
the step below the bf16 that the configuration states.  On the chip the
readings are taken at each cell's own size (PERF.md); here at the smallest
size at which the comparison separates the two on the CPU: at a smoke
width of 64 and 256 tokens of vocabulary, near-ties are so rare that both
often read 0.
"""

from bench import calibrate
from bench.tests import smoke

CFG = dict(
    smoke.GQA, name="control-size", vocab_size=8192, hidden_size=128, intermediate_size=256,
    # CPU readings, 2 s windows: sound 0.000082-0.000323 over 4 seeds,
    # control 0.002456-0.00276 over 3
    correct={"mean_logit_gap": 0.0008},
)
MIX = dict(smoke.CLOSED, output=dict(smoke.CLOSED["output"], median=24, min=16, max=40))


def test_program_passes_and_its_int8_path_fails():
    # a wall-clock window: a busy CPU serves fewer tokens in it
    sound = calibrate.reading(CFG, MIX, 1000, 6.0, control=False)
    control = calibrate.reading(CFG, MIX, 2000, 6.0, control=True)
    limit = CFG["correct"]["mean_logit_gap"]
    assert sound["compared_tokens"] >= 100 and control["compared_tokens"] >= 100
    assert sound["mean_logit_gap"] <= limit < control["mean_logit_gap"]
