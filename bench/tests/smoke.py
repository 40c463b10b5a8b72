"""A benchmark tree at smoke sizes, for the CPU tests: two cells of the real
families and mixes' shapes, tiny widths, the real metric readers."""

from __future__ import annotations

import json
import os

from bench.lib import spec

GQA = {
    "family": "gqa", "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 128, "vocab_size": 256, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "tie_word_embeddings": False, "compute_dtype": "bfloat16",
    "correct": {"mean_logit_gap": 0.01},
}
MLA = {
    "family": "mla", "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 128, "vocab_size": 256, "q_lora_rank": 32,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "tie_word_embeddings": False,
    "compute_dtype": "bfloat16", "correct": {"mean_logit_gap": 0.01},
}
CLOSED = {
    "loop": "closed", "clients": 4, "pool": 64, "slots": 4, "chunk_size": 16, "chunk_budget": 1,
    "prompt": {"median": 12, "sigma": 0.6, "min": 4, "max": 40},
    "output": {"median": 6, "sigma": 0.5, "min": 3, "max": 12},
}
OPEN = dict(CLOSED, loop="open", rate=4.0, ramp_s=0.5)
OPEN.pop("clients")
OPEN.pop("pool")


def write_tree(root: str, extra_cells=()) -> dict:
    """BENCHMARK.json plus configuration and mix files under ``root``; the
    metric readers are the real ones (linked)."""
    os.makedirs(os.path.join(root, "bench", "configs"), exist_ok=True)
    os.makedirs(os.path.join(root, "bench", "traffic"), exist_ok=True)
    os.symlink(os.path.join(spec.BENCH_DIR, "metrics"), os.path.join(root, "bench", "metrics"))
    for name, cfg in (("gqa-smoke", GQA), ("mla-smoke", MLA)):
        with open(os.path.join(root, "bench", "configs", f"{name}.json"), "w") as f:
            json.dump(cfg, f)
    for name, mix in (("closed", CLOSED), ("open", OPEN)):
        with open(os.path.join(root, "bench", "traffic", f"{name}.json"), "w") as f:
            json.dump(mix, f)
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    bench = {
        "command": real["command"], "paths": real["paths"], "run_seconds": 3,
        "configs": [
            {"name": n, "source": "smoke", "file": f"bench/configs/{n}.json", "reduced": [], "why": "smoke"}
            for n in ("gqa-smoke", "mla-smoke")
        ],
        "workloads": [
            {"name": "gqa.closed", "config": "gqa-smoke", "traffic": "closed", "chips": 1, "why": "smoke"},
            {"name": "mla.open", "config": "mla-smoke", "traffic": "open", "chips": 1, "why": "smoke"},
            *extra_cells,
        ],
        "end_to_end": real["end_to_end"],
        "per_layer": [
            dict(m, workloads=["gqa.closed", "mla.open"]) for m in real["per_layer"]
        ],
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return bench
