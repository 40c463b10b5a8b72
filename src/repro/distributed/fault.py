"""Fault tolerance & straggler mitigation at the job level.

Inside one pod, SPMD execution is synchronous at the XLA level -- there are
no per-step stragglers to mitigate *within* a program; the failure modes
that matter at 1000+ nodes are (a) a host/chip dying (job aborts, must
restart from checkpoint), (b) a pod-wide slowdown or loss (elastic
downsize), and (c) transient runtime errors.  This module provides the
single-controller primitives for all three; the multi-host versions use the
same logic keyed on ``jax.process_index()``.

  Heartbeat        liveness file per host; the launcher's watchdog treats a
                   stale heartbeat as a dead worker and triggers restart.
  restart_loop     supervisor that re-invokes a job function after failures,
                   restoring from the latest complete checkpoint each time
                   (crash-consistent by the DONE-marker protocol in
                   checkpoint/ckpt.py).
  elastic_meshes   the downsize ladder: (2,16,16) -> (16,16) -> (8,16) ...,
                   used when a restart finds fewer live devices; checkpoint
                   restore re-shards to whatever mesh is available
                   (restore_checkpoint(shardings=...)).
"""

from __future__ import annotations

import json
import os
import time
from typing import Callable

import jax


class Heartbeat:
    def __init__(self, path: str, host: int = 0):
        self.file = os.path.join(path, f"heartbeat_{host:05d}.json")
        os.makedirs(path, exist_ok=True)

    def beat(self, step: int) -> None:
        tmp = self.file + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"t": time.time(), "step": step}, f)
        os.replace(tmp, self.file)

    @staticmethod
    def stale_hosts(path: str, timeout_s: float = 300.0) -> list[int]:
        now = time.time()
        dead = []
        if not os.path.isdir(path):
            return dead
        for name in os.listdir(path):
            if not name.startswith("heartbeat_") or name.endswith(".tmp"):
                continue
            with open(os.path.join(path, name)) as f:
                info = json.load(f)
            if now - info["t"] > timeout_s:
                dead.append(int(name.split("_")[1].split(".")[0]))
        return sorted(dead)


def elastic_meshes() -> list[tuple[tuple[int, ...], tuple[str, ...]]]:
    """The downsize ladder a restarted job walks until a mesh fits the
    surviving device count."""
    return [
        ((2, 16, 16), ("pod", "data", "model")),
        ((16, 16), ("data", "model")),
        ((8, 16), ("data", "model")),
        ((4, 16), ("data", "model")),
    ]


def pick_mesh_for(n_devices: int) -> jax.sharding.Mesh:
    """Largest ladder mesh that fits the live device count."""
    import math

    from repro.launch.mesh import make_mesh

    for shape, axes in elastic_meshes():
        if math.prod(shape) <= n_devices:
            return make_mesh(shape, axes)
    # last resort: whatever we have as pure DP
    return make_mesh((n_devices, 1), ("data", "model"))


def restart_loop(
    job: Callable[[int], None],
    *,
    max_restarts: int = 3,
    backoff_s: float = 1.0,
) -> int:
    """Run ``job(attempt)``; on failure restart up to max_restarts times.
    The job is responsible for resuming from its checkpoint (Trainer
    .try_resume()).  Returns the number of restarts consumed."""
    for attempt in range(max_restarts + 1):
        try:
            job(attempt)
            return attempt
        except Exception:
            if attempt == max_restarts:
                raise
            time.sleep(backoff_s * (2**attempt))
    return max_restarts  # pragma: no cover
