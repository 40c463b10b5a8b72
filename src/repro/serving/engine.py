"""Batched serving engine: synchronized prefill -> decode, plus the per-slot
primitives the continuous-batching scheduler drives.

The engine owns the jitted prefill and decode step (cache donated between
steps so decode is allocation-free) and a greedy/temperature sampler.  Two
serving modes share those compiled functions:

  * **synchronized batched decode** (``generate``): every slot advances one
    token per step at a common depth -- the mode the ``decode_32k`` /
    ``long_500k`` shape cells model;
  * **continuous batching** (``repro.serving.scheduler`` +
    ``repro.serving.kvpool``): the decode step takes a per-slot position
    *vector*, so slots sitting at different depths advance in one step.  The
    engine contributes ``prefill_request`` (batch-1 prefill that does NOT
    touch the resident synchronized cache), ``prefill_chunk`` (advance one
    request's prefill by one bucketed chunk at its absolute offset -- the
    primitive behind the scheduler's mixed prefill/decode steps, DESIGN.md
    §8.1), and ``decode_slots`` (vector-pos decode over an externally owned
    cache pytree); request lifecycle and KV row management live in the
    scheduler/pool.

Empty or cleared slots are marked ``pos = -1`` everywhere; the attention
masking rule ``valid(k) = pos[k] >= 0`` then blanks their cache rows, so a
freed slot can never attend to a previous request's keys.

Decode-shape plans: the per-step dense GEMMs of a decode token are all
``(batch, *) x (*, *)`` problems, so the batch geometry the scheduler picks
determines which kernel plans fire.  ``decode_plans`` consults the
``repro.tune`` plan cache (PR 1) for every such problem, letting launchers
and benchmarks report whether the serving batch runs on measured winners or
on the analytical fallback.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.registry import Model
from repro.obs import attribution as _obs
from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace
from repro.serving.kvpool import clear_slots


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_len: int
    batch: int  # synchronized batch size == continuous-batching slot count
    temperature: float = 0.0  # 0 => greedy
    seed: int = 0


def chunk_schedule(n_tokens: int, chunk: int) -> list[tuple[int, int]]:
    """Split a prompt into schedulable prefill chunks: [(offset, length), ...].

    The bucketing rule that keeps chunk shapes cacheable (one jit compile
    and one ``repro.tune`` plan-cache row per shape, DESIGN.md §8): as many
    full ``chunk``-length pieces as fit, then the remainder split greedily
    into power-of-two buckets.  Distinct lengths are therefore bounded by
    log2(chunk) + 2 regardless of the prompt-length distribution -- the
    serving analogue of padding GEMMs to block multiples, except nothing is
    padded (a padded tail would write phantom positions into the KV slot).
    """
    if n_tokens < 1:
        raise ValueError(f"n_tokens must be >= 1, got {n_tokens}")
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    out, off = [], 0
    while n_tokens - off >= chunk:
        out.append((off, chunk))
        off += chunk
    rem = n_tokens - off
    bucket = 1 << (chunk.bit_length() - 1)  # largest power of two <= chunk
    while rem:
        while bucket > rem:
            bucket >>= 1
        out.append((off, bucket))
        off += bucket
        rem -= bucket
    return out


# ---------------------------------------------------------------------------
# Decode-shape plan consultation (the repro.tune cache, PR 1)
# ---------------------------------------------------------------------------


def decode_gemm_problems(cfg, batch: int) -> list[tuple[str, int, int, int]]:
    """The per-token dense GEMM problems of one decode step: (name, M, N, K).

    M is the serving batch (slot count) -- the knob the scheduler owns; N/K
    come from the architecture.  MoE expert GEMMs route through the grouped
    kernel and are tuned under its own backend key, so only the dense
    projections are listed here.
    """
    d = cfg.d_model
    probs: list[tuple[str, int, int, int]] = []
    if cfg.attention == "mla":
        m = cfg.mla
        qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
        probs += [
            ("wq_a", batch, m.q_lora_rank, d),
            ("wq_b", batch, cfg.n_heads * qk_head, m.q_lora_rank),
            ("wkv_a", batch, m.kv_lora_rank + m.qk_rope_head_dim, d),
            ("wo", batch, d, cfg.n_heads * m.v_head_dim),
        ]
    elif cfg.attention in ("gqa", "swa"):
        hd = cfg.resolved_head_dim
        probs += [
            ("wq", batch, cfg.n_heads * hd, d),
            ("wk", batch, cfg.n_kv_heads * hd, d),
            ("wv", batch, cfg.n_kv_heads * hd, d),
            ("wo", batch, d, cfg.n_heads * hd),
        ]
    if cfg.moe is None and cfg.d_ff:
        probs += [
            ("ffn_in", batch, cfg.d_ff, d),
            ("ffn_out", batch, d, cfg.d_ff),
        ]
    return probs


def consult_decode_plans(cfg, batch: int, chip=None) -> dict:
    """Look every decode-step GEMM up in the repro.tune plan cache.

    Returns ``{name: ((m, n, k), TunedPlan | None)}`` -- None means the
    analytical heuristic will drive that projection.  Never raises: the
    autotuner is an accelerant, not a dependency.
    """
    try:
        from repro.core import hw
        from repro.tune import cache as tune_cache
    except ImportError:  # pragma: no cover
        return {}
    chip = hw.get_chip(chip)
    dtype = str(jnp.dtype(cfg.dtype))
    out = {}
    for name, m, n, k in decode_gemm_problems(cfg, batch):
        plan = tune_cache.lookup_block("pallas-systolic", chip.name, m, n, k, dtype)
        out[name] = ((m, n, k), plan)
    return out


class ServeEngine:
    def __init__(
        self,
        model: Model,
        params: Any,
        scfg: ServeConfig,
        mesh: jax.sharding.Mesh | None = None,
    ):
        """``mesh`` opts into tensor-parallel serving (DESIGN.md §6): params
        are TP-sharded by the ``distributed.sharding`` rules, every jitted
        step traces under the mesh with activation annotations enabled, and
        GSPMD propagates the layout through prefill caches and decode steps.
        ``mesh=None`` is the unchanged single-device engine."""
        self.model = model
        self.cfg = model.cfg
        self.scfg = scfg
        self.mesh = mesh
        if mesh is not None:
            from repro.distributed import sharding as dist_sharding

            tp = mesh.shape.get("model", 1)
            n_heads = getattr(model.cfg, "n_heads", None)
            if n_heads and tp > n_heads:
                import warnings

                warnings.warn(
                    f"model-parallel degree {tp} exceeds n_heads={n_heads}: "
                    "the packed QKV sharding then splits the rotary head_dim "
                    "across devices, which is the wrong TP layout (shard "
                    "heads, not head_dim) and miscompiles on XLA:CPU forced "
                    f"meshes; use tp <= {n_heads}."
                )
            p_sh = dist_sharding.param_shardings(params, mesh)
            params = jax.device_put(params, p_sh)
        self.params = params

        # Named, so that a profiler trace's ``XLA Modules`` line tells the
        # steps apart: jit_prefill, jit_decode_step, jit_prefill_chunk.
        def prefill(p, b):
            return model.prefill(p, b, max_len=scfg.max_len)

        def decode_step(p, t, c, pos):
            return model.decode_step(p, t, cache=c, pos=pos)

        def prefill_chunk(p, t, c, off, wrapped):
            return model.prefill_chunk(
                p, {"tokens": t}, cache=c, offset=off, wrapped=wrapped
            )

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode_step, donate_argnums=(2,))
        self._chunk = jax.jit(
            prefill_chunk, static_argnums=(4,), donate_argnums=(2,)
        )
        self._key = jax.random.PRNGKey(scfg.seed)
        self.cache = None
        self.pos = 0
        self._decode_plans: dict | None = None
        # GEMM-work accounting (DESIGN.md §11).  core.ops.matmul records at
        # *trace* time, so each totals object accumulates exactly one traced
        # step's FLOPs + roofline prediction: the first call through a jitted
        # function populates it, cached executions add nothing.  The same
        # trace-time rule applies to the process-wide ``gemm.*`` counters --
        # ``gemm.calls`` counts *compiles*, not executions.  The execution
        # count lives in the ``engine.steps{phase}`` counter each public
        # step method increments (one per call, warmup included), so an MFU
        # denominator is auditable from a snapshot alone:
        # total FLOPs(phase) = totals.flops * engine.steps{phase}.
        # Separate totals objects per call path, each path its own compile:
        #   decode_totals    vector-pos decode_slots (one continuous tick)
        #   generate_totals  synchronized scalar-pos decode step
        #   prefill_totals   monolithic prefills (aggregate across shapes)
        #   chunk totals     per (bucketed length, wrapped) prefill chunk
        self.decode_totals = _obs.GemmTotals()
        self.generate_totals = _obs.GemmTotals()
        self.prefill_totals = _obs.GemmTotals()
        self._chunk_totals: dict[tuple[int, bool], _obs.GemmTotals] = {}

    @contextlib.contextmanager
    def _mesh_scope(self):
        """Trace/run scope: no-op single-device, or the TP mesh context with
        the opt-in activation-sharding annotations enabled."""
        if self.mesh is None:
            yield
        else:
            from repro.distributed import annotate

            with self.mesh, annotate.annotations(self.mesh):
                yield

    # -- sampling --------------------------------------------------------------

    def _sample(self, logits: jax.Array) -> jax.Array:
        """logits: (B, 1[, ncb], V) -> tokens (B, 1[, ncb]) int32."""
        if self.scfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self._key, sub = jax.random.split(self._key)
        return jax.random.categorical(
            sub, logits / self.scfg.temperature, axis=-1
        ).astype(jnp.int32)

    # -- decode-shape plans ----------------------------------------------------

    @property
    def decode_plans(self) -> dict:
        """Tune-cache consultation for this engine's decode batch geometry
        (lazy; see ``consult_decode_plans``)."""
        if self._decode_plans is None:
            self._decode_plans = consult_decode_plans(self.cfg, self.scfg.batch)
        return self._decode_plans

    def decode_plan_report(self) -> str:
        """One-line summary: how many decode GEMMs run on tuned plans."""
        plans = self.decode_plans
        hits = sum(1 for _, p in plans.values() if p is not None)
        return f"decode plans: {hits}/{len(plans)} tuned (batch={self.scfg.batch})"

    # -- synchronized serving --------------------------------------------------

    def prefill(self, batch: dict) -> jax.Array:
        """Prime the resident cache from a synchronized prompt batch; returns
        the first sampled continuation token (prefill emits last-position
        logits)."""
        _obs_metrics.inc("engine.steps", phase="prefill")
        with self._mesh_scope(), _obs.collecting(self.prefill_totals):
            logits, self.cache = self._prefill(self.params, batch)
        self.pos = self.prompt_positions(batch)
        return self._sample(logits)

    def decode(self, tokens: jax.Array, n_steps: int) -> jax.Array:
        """Generate n_steps tokens.  tokens: (B, 1[, ncb]) seed tokens.
        Returns (B, n_steps[, ncb])."""
        if self.cache is None:
            raise RuntimeError("prefill() first")
        outs = []
        tok = tokens
        _obs_metrics.inc("engine.steps", n_steps, phase="decode_sync")
        with self._mesh_scope(), _obs.collecting(self.generate_totals):
            for _ in range(n_steps):
                logits, self.cache = self._decode(
                    self.params, tok, self.cache, jnp.int32(self.pos)
                )
                tok = self._sample(logits)
                outs.append(tok)
                self.pos += 1
        return jnp.concatenate(outs, axis=1)

    def generate(self, batch: dict, n_steps: int) -> jax.Array:
        first = self.prefill(batch)
        rest = self.decode(first, n_steps - 1) if n_steps > 1 else None
        return first if rest is None else jnp.concatenate([first, rest], axis=1)

    def reset_slots(self, slot_mask: jax.Array) -> None:
        """Clear finished slots (continuous-batching rotation): zero their
        float cache state and set their position arrays to -1 so the freed
        slot's old keys are masked out of every later step (``pos = 0`` is a
        valid position -- see ``kvpool.clear_slots``)."""
        if self.cache is None:
            return
        self.cache = clear_slots(
            self.cache, jnp.asarray(slot_mask), self.scfg.batch
        )

    # -- continuous-batching primitives ---------------------------------------

    def prompt_positions(self, batch: dict) -> int:
        """Positions a prompt occupies in the cache (incl. non-text prefix)."""
        n = batch["tokens"].shape[1]
        if self.cfg.frontend == "vit":
            n += self.cfg.n_patches
        return n

    def prefill_request(self, batch: dict):
        """Prefill one admission unit WITHOUT touching the resident cache.

        batch is a batch-1 prompt dict; returns (first sampled token
        (1, 1[, ncb]), primed batch-1 cache at this engine's max_len) for the
        KV pool to scatter into the assigned slot.
        """
        _obs_metrics.inc("engine.steps", phase="prefill_request")
        with self._mesh_scope(), _obs.collecting(self.prefill_totals), \
                _obs_trace.span(
                    "engine.prefill_request",
                    cat="engine",
                    prompt_len=batch["tokens"].shape[1],
                ):
            logits, cache = self._prefill(self.params, batch)
        return self._sample(logits), cache

    # -- chunked prefill -------------------------------------------------------

    @property
    def supports_chunked_prefill(self) -> bool:
        """Every family except the vit frontend (its patch prefix is glued
        to the first text positions); the scheduler falls back to monolithic
        ``prefill_request`` when False."""
        return self.cfg.frontend != "vit"

    @property
    def chunk_prefill_staged(self) -> bool:
        """True when mid-prefill chunks must carry a request-private staging
        cache instead of round-tripping through the KV pool.  Attention
        caches are safe in the pool mid-prefill -- the ``pos`` validity rule
        leaves a masked slot's rows bit-for-bit untouched under co-scheduled
        decode steps -- but SSM/hybrid *state* leaves have no such mask (a
        decode step advances every batch row unconditionally), so their
        chunks accumulate privately and the slot is written once, on the
        final chunk, exactly like the monolithic contract."""
        return self.cfg.family in ("ssm", "hybrid")

    def attn_cache_len(self) -> int:
        """Sequence capacity of the per-layer attention cache: ``max_len``,
        except the SWA ring which only keeps ``window`` slots."""
        if self.cfg.attention == "swa":
            return min(self.scfg.max_len, self.cfg.window)
        return self.scfg.max_len

    def prefill_chunk(self, tokens, cache_one, offset: int, *, last: bool):
        """Advance one request's prefill by one chunk.

        tokens: (1, L[, ncb]) slice of the prompt at absolute offset
        ``offset``; cache_one: the request's batch-1 slot view (donated).
        Returns (first sampled token (1, 1[, ncb]) when ``last`` else None,
        advanced cache).  ``offset`` is traced, so chunks of one (bucketed)
        length share a compile; the SWA ring-wrap variant is a separate
        static compile (see ``attention.gqa_prefill_chunk``).
        """
        length = tokens.shape[1]
        wrapped = offset + length > self.attn_cache_len()
        totals = self._chunk_totals.setdefault(
            (length, wrapped), _obs.GemmTotals()
        )
        _obs_metrics.inc("engine.steps", phase="prefill_chunk")
        with self._mesh_scope(), _obs.collecting(totals), \
                _obs_trace.span(
                    "engine.prefill_chunk",
                    cat="engine",
                    offset=offset,
                    length=length,
                    wrapped=wrapped,
                ):
            logits, cache_one = self._chunk(
                self.params,
                jnp.asarray(tokens),
                cache_one,
                jnp.int32(offset),
                wrapped,
            )
        return (self._sample(logits) if last else None), cache_one

    def decode_slots(self, tokens: jax.Array, cache: Any, pos: jax.Array):
        """One continuous-batching decode step over an external cache.

        tokens: (B, 1[, ncb]) last token per slot (garbage for empty slots);
        pos: (B,) int32 per-slot absolute positions, -1 for empty slots.
        Returns (sampled tokens (B, 1[, ncb]), new cache).  The cache is
        donated, matching the synchronized path's allocation-free decode.
        """
        _obs_metrics.inc("engine.steps", phase="decode")
        with self._mesh_scope(), _obs.collecting(self.decode_totals), \
                _obs_trace.span(
                    "engine.decode_slots", cat="engine", batch=tokens.shape[0]
                ):
            logits, cache = self._decode(self.params, tokens, cache, pos)
        return self._sample(logits), cache
