"""The harness end to end on the CPU at smoke sizes (Pallas in interpret mode).

``run.main(..., require_tpu=False)`` skips only the look for a chip; the rest
of a run (set-up, window, trace reduction, reference comparison, result
line) is the one the chip runs.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import run
from bench.lib import spec
from bench.tests import smoke

SEED = 2**40 + 12345  # wider than 32 bits, as the driver's are


@pytest.fixture
def tree(tmp_path):
    smoke.write_tree(str(tmp_path))
    return str(tmp_path)


def _run(root, workload, trace=0, seconds=2.0, tamper=None, seed=SEED):
    args = ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    return run.main(args, require_tpu=False, root=root, tamper=tamper)


@pytest.mark.parametrize("workload,trace", [("gqa.closed", 0), ("mla.open", 1)])
def test_cell_runs_and_is_correct(tree, workload, trace, capsys):
    res = _run(tree, workload, trace)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    if trace:
        assert {"slot_occupancy", "queue_wait_p95_ms", "mfu.decode"} - {"mfu.decode"} <= set(res["metrics"])
        assert "busy_s" in res["device"] and "window_s" in res["device"]
    else:
        assert set(res["metrics"]) == {"out_tok_s", "ttft_p95_ms", "itl_p95_ms", "setup_s"}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    out = capsys.readouterr()
    assert json.loads(out.out.strip().splitlines()[-1]) == json.loads(json.dumps(res))
    assert out.err.strip().splitlines()[-1].startswith("check compared_tokens")


def test_same_seed_same_traffic_and_weights(tree):
    from bench.lib import traffic

    mix = spec.traffic("open", os.path.join(tree, "bench"))
    a = traffic.Generator(mix, 256, SEED, 10)
    b = traffic.Generator(mix, 256, SEED, 10)
    c = traffic.Generator(mix, 256, SEED + 1, 10)
    assert [(i.prompt_len, i.out_len, i.due) for i in a.items] == [(i.prompt_len, i.out_len, i.due) for i in b.items]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a.items, b.items))
    # another seed: the same set of sizes and gaps, in another order
    assert sorted(i.prompt_len for i in a.items) == sorted(i.prompt_len for i in c.items)
    assert [i.prompt_len for i in a.items] != [i.prompt_len for i in c.items]


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_a_schedule_seed_fixes_sizes_and_arrivals(loop):
    from bench.lib import traffic

    mix = dict(smoke.OPEN if loop == "open" else smoke.CLOSED, schedule_seed=7)
    a = traffic.Generator(mix, 256, SEED, 10)
    c = traffic.Generator(mix, 256, SEED + 1, 10)
    assert [(i.prompt_len, i.out_len, i.due) for i in a.items] == [(i.prompt_len, i.out_len, i.due) for i in c.items]
    assert not all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a.items, c.items))


def test_a_new_mix_file_and_entry_are_picked_up(tmp_path):
    extra = {"name": "gqa.open2", "config": "gqa-smoke", "traffic": "open2", "chips": 1, "why": "added"}
    smoke.write_tree(str(tmp_path), extra_cells=[extra])
    mix = dict(smoke.OPEN, rate=6.0)
    with open(tmp_path / "bench" / "traffic" / "open2.json", "w") as f:
        json.dump(mix, f)
    res = _run(str(tmp_path), "gqa.open2")
    assert res["correct"], res["checks"]


def test_a_token_altered_where_it_is_produced_fails(tree):
    def tamper(cell):
        sample = cell.engine._sample
        vocab = cell.cfg["vocab_size"]
        cell.engine._sample = lambda logits: (sample(logits) + 1) % vocab

    res = _run(tree, "gqa.closed", tamper=tamper)
    assert not res["correct"]
    gap = next(c for c in res["checks"] if c["name"] == "mean_logit_gap")
    assert gap["value"] > gap["limit"]


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"), "--workload",
         "internlm2-chat-saturated", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "correct" not in p.stdout


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    code = (
        "import sys; sys.path.insert(0, '.'); from bench import run; "
        "run.main(['--workload', 'internlm2-chat-saturated', '--seed', '1', '--seconds', '1', "
        "'--trace', '0'], require_tpu=False, root='.')"
    )
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
