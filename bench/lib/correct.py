"""Whether the timed path served the right tokens.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed with the longest among
them, goes through the plain reference (``reference.served_gaps``): the prompt
and the served tokens, teacher-forced in float32.  Compared, each against a
limit of its own:

* ``mean_logit_gap``: the mean, over every served token of the sample, of
  how far the reference's best logit lies above the served token's.  Greedy
  bf16 serving reads a small gap (rounding swaps a few near-ties); a lower
  precision swaps more and farther, and a wrong token lies far below.  The
  limit is the configuration's ``correct.mean_logit_gap``; how it was set is
  in PERF.md.  (The widest gap, printed beside it, is an extreme of the
  near-ties and swings too much from seed to seed to separate bf16 from
  int8; see PERF.md.)
* ``wrong_lengths``: finished requests that did not get exactly the tokens
  they asked for (limit 0).
* ``bad_token_ids``: served ids outside the vocabulary (limit 0).
* ``compared_tokens``: served tokens the sample holds; under its limit (a
  floor), the comparison saw too little to say anything.
"""

from __future__ import annotations

import numpy as np

from bench.lib import reference, traffic

TARGET_TOKENS = 384  # served tokens to compare, at the least ...
MAX_REQUESTS = 16  # ... unless this many requests come first
MIN_TOKENS = 32
_FORWARDS: dict = {}  # (family, configuration) -> reference forward, made once


def sample(recs: list, seed: int) -> list:
    """Of the requests the window finished (``recs``), those to compare: the
    longest, then others in an order drawn from the seed, until
    TARGET_TOKENS served tokens or MAX_REQUESTS."""
    done = recs
    if not done:
        return []
    longest = max(done, key=lambda r: (r.item.prompt_len + len(r.req.out), r.item.idx))
    rest = [r for r in done if r is not longest]
    order = traffic.rng_for(seed, 3).permutation(len(rest))
    out, n = [longest], len(longest.req.out)
    for i in order:
        if n >= TARGET_TOKENS or len(out) >= MAX_REQUESTS:
            break
        out.append(rest[i])
        n += len(rest[i].req.out)
    return out


def gaps(cell, window) -> np.ndarray:
    """The gap of every served token of the sample (see ``sample``)."""
    cfg, vocab = cell.cfg, cell.cfg["vocab_size"]
    key = (cfg["family"], cfg["name"])
    if key not in _FORWARDS:
        _FORWARDS[key] = cell.fam.hidden_fn(cfg)
    t_pad = reference.pad_len(cell.max_len)
    r_pad = cell.mix["output"]["max"]
    out = []
    for r in sample(window.finished(), cell.seed):
        served = np.clip(_ids(r), 0, vocab - 1).astype(np.int32)
        out.append(reference.served_gaps(_FORWARDS[key], cell.weights, r.item.tokens, served, t_pad, r_pad))
    return np.concatenate(out) if out else np.zeros(0)


def _ids(rec) -> np.ndarray:
    return np.asarray([int(np.asarray(t).reshape(-1)[0]) for t in rec.req.out], np.int64)


def check(cell, window, g: np.ndarray | None = None) -> list:
    """[(name, value, limit, kind)] with kind "max" (value <= limit passes)
    or "min" (value >= limit passes); ``g``: the sample's gaps, if known."""
    vocab = cell.cfg["vocab_size"]
    wrong_len = sum(len(r.req.out) != r.item.out_len for r in window.finished())
    bad_ids = sum(int(np.sum((_ids(r) < 0) | (_ids(r) >= vocab))) for r in window.recs)
    g = gaps(cell, window) if g is None else g
    return [
        ("mean_logit_gap", float(g.mean()) if g.size else float("inf"),
         float(cell.cfg["correct"]["mean_logit_gap"]), "max"),
        ("wrong_lengths", wrong_len, 0, "max"),
        ("bad_token_ids", bad_ids, 0, "max"),
        ("compared_tokens", int(g.size), MIN_TOKENS, "min"),
    ]


def passed(checks: list) -> bool:
    return all(v <= lim if kind == "max" else v >= lim for _, v, lim, kind in checks)
