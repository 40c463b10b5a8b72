"""Slot-pooled KV cache manager for continuous batching.

The pool owns one batched decode cache (``model.init_cache(n_slots, ...)``)
whose batch axis is a pool of *slots*; each slot holds at most one in-flight
request.  The layout invariants it relies on:

  * every cache leaf from ``transformer.init_cache`` carries the batch axis
    at position 1 (axis 0 is the stacked layer/group dim), so writing one
    slot is a single ``dynamic_update_slice_in_dim(axis=1)`` per leaf and
    works identically for GQA/SWA/MLA KV caches and SSM/hybrid state caches;
  * the caches' per-slot absolute-position arrays (``pos``, the only integer
    leaves) drive the attention masking rule ``valid(k) = pos[k] >= 0``.  A
    free slot is ``pos = -1`` everywhere, which makes its old keys
    unreachable the moment the slot is released -- freeing is a masking
    operation, not (only) a zeroing one.

Host-side, ``positions[slot]`` mirrors the device state: the next absolute
position the slot will write (prompt length right after admission, +1 per
decoded token), or -1 while free.  That vector, as ``pos_vector()``, is
exactly the per-slot position argument of the vector-``pos`` decode step.

Chunked prefill round-trips a slot through ``gather_slot`` (batch-1 view)
and ``write_slot`` (scatter back; ``next_pos=None`` mid-prefill): chunk K/V
rows land in the pool at their absolute offsets while ``positions[slot]``
stays -1, so a partially prefilled slot is invisible to decode steps under
the same masking rule that protects freed slots.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import profile as _obs_profile
from repro.obs import trace as _obs_trace

# ---------------------------------------------------------------------------
# Quantized pool storage (kv8, DESIGN.md §10).
#
# With ``quantize_kv`` the pool's *resident* form is int8 values plus fp32
# per-(layer, slot[, head]) scales; the fp pytree the engine's decode step
# consumes is materialised on access and re-quantized on assignment, so the
# scheduler drives the same ``pool.cache`` interface either way.  Scales are
# symmetric absmax over each slot's sequence/feature dims -- freeing a slot
# zeroes its floats, so a freed slot quantizes to exact zeros and stays
# unreachable behind the same ``pos = -1`` validity mask that protects the
# fp pool (quantization noise on masked rows is never observable).
# ---------------------------------------------------------------------------

_KV_QMAX = 127.0
_KV_KEYS = frozenset({"qv", "qs"})


def _kv_quantizable(leaf: jax.Array) -> bool:
    """Float cache state with a slot axis: attention K/V (and MLA latents).
    Integer leaves are the ``pos`` masks and always stay exact."""
    return jnp.issubdtype(leaf.dtype, jnp.floating) and leaf.ndim >= 3


def _kv_scale_axes(leaf: jax.Array) -> tuple[int, ...]:
    """Reduce absmax over everything except layer (0), slot (1), and --
    for (L, B, S, H, hd) attention caches -- the head axis (3): the
    per-head-per-slot scale granularity."""
    keep = {0, 1} | ({3} if leaf.ndim >= 5 else set())
    return tuple(i for i in range(leaf.ndim) if i not in keep)


def _is_qleaf(node: Any) -> bool:
    return isinstance(node, dict) and set(node) == _KV_KEYS


@jax.jit
def quantize_kv(cache: Any) -> Any:
    """fp cache pytree -> quantized pool form ({"qv": int8, "qs": fp32})."""

    def q(leaf):
        if not _kv_quantizable(leaf):
            return leaf
        x = leaf.astype(jnp.float32)
        absmax = jnp.max(jnp.abs(x), axis=_kv_scale_axes(leaf), keepdims=True)
        qs = jnp.where(absmax > 0, absmax / _KV_QMAX, 1.0)
        qv = jnp.clip(jnp.round(x / qs), -_KV_QMAX, _KV_QMAX).astype(jnp.int8)
        return {"qv": qv, "qs": qs}

    return jax.tree.map(q, cache)


@functools.partial(jax.jit, static_argnames=("dtype",))
def dequantize_kv(qcache: Any, dtype: str) -> Any:
    """Quantized pool form -> fp cache pytree at ``dtype``."""

    def dq(node):
        if _is_qleaf(node):
            return (node["qv"].astype(jnp.float32) * node["qs"]).astype(dtype)
        return node

    return jax.tree.map(dq, qcache, is_leaf=_is_qleaf)


def check_next_pos(next_pos: Any) -> int | None:
    """Validate a ``write_slot`` position against the validity-mask contract.

    The whole masking rule is ``valid(k) = pos[k] >= 0`` with -1 the one
    freed/empty sentinel, so any position below -1 (or a NaN/non-integral
    value smuggled in through a float) would create a slot state no reader
    is specified for.  Rejecting it here -- before the cache scatter --
    keeps a bad caller from mutating the pool and *then* failing.  (The
    matching static rule is repro.check's ``pos-mask-update``.)
    """
    if next_pos is None:
        return None
    f = float(next_pos)
    if f != f or f != int(f):  # NaN or non-integral
        raise ValueError(
            f"write_slot: next_pos must be an integer, got {next_pos!r}"
        )
    p = int(f)
    if p < -1:
        raise ValueError(
            f"write_slot: next_pos must be >= -1 (-1 = empty sentinel), got {p}"
        )
    return p


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_slot(pool: Any, one: Any, slot: jax.Array) -> Any:
    """Write a batch-1 cache pytree into slot ``slot`` of the pooled cache."""
    return jax.tree.map(
        lambda p, o: jax.lax.dynamic_update_slice_in_dim(
            p, o.astype(p.dtype), slot, axis=1
        ),
        pool,
        one,
    )


@jax.jit
def _gather_slot(pool: Any, slot: jax.Array) -> Any:
    """Batch-1 copy of slot ``slot`` from the pooled cache (not donated --
    the pool stays live while the copy is advanced by a prefill chunk)."""
    return jax.tree.map(
        lambda p: jax.lax.dynamic_slice_in_dim(p, slot, 1, axis=1), pool
    )


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def clear_slots(cache: Any, slot_mask: jax.Array, batch: int) -> Any:
    """Clear masked slots in every cache leaf with a (.., batch, ..) axis 1.

    The one implementation of the slot-clearing invariant (shared by
    ``KVPool.free`` and ``ServeEngine.reset_slots``): float state is zeroed,
    while integer leaves -- the per-slot absolute-position arrays -- are set
    to **-1**, because ``pos = 0`` is a *valid* position under the masking
    rule ``valid(k) = pos[k] >= 0``; zeroing them would leave the stale key
    written at slot 0 attendable by the next request.
    """

    def clear(leaf):
        if leaf.ndim >= 2 and leaf.shape[1] == batch:
            shape = (1, batch) + (1,) * (leaf.ndim - 2)
            m = slot_mask.reshape(shape).astype(bool)
            if jnp.issubdtype(leaf.dtype, jnp.integer):
                return jnp.where(m, -1, leaf)
            return jnp.where(m, 0, leaf).astype(leaf.dtype)
        return leaf

    return jax.tree.map(clear, cache)


class KVPool:
    """Fixed-size pool of KV/state cache slots shared by in-flight requests.

    ``quantize_kv=True`` keeps the resident pool in int8 with per-head-per-
    slot fp32 scales (kv8): the ``cache`` property dequantizes on read and
    re-quantizes on write, so every consumer -- decode steps, slot
    gather/scatter, slot clearing -- sees the usual fp pytree while the
    pool's steady-state memory is ~1/2 (bf16) to ~1/4 (fp32) of the fp
    form.  Only float leaves with a slot axis quantize; the integer ``pos``
    validity masks stay exact, so the freed/mid-prefill-slot invariants are
    unchanged.
    """

    def __init__(
        self, model, n_slots: int, max_len: int, dtype=None, quantize_kv_cache: bool = False
    ):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.model = model
        self.n_slots = n_slots
        self.max_len = max_len
        self.dtype = dtype or jnp.dtype(model.cfg.dtype)
        self.quantize_kv = quantize_kv_cache
        self._qcache = None
        self._cache = None
        self.cache = model.init_cache(n_slots, max_len, self.dtype)
        self.positions = np.full((n_slots,), -1, np.int64)
        # LIFO free list: the most recently freed slot is reused first, which
        # keeps the active slots dense in low indices under light load.
        self._free = list(range(n_slots - 1, -1, -1))

    # -- resident storage (fp, or int8 + scales under kv8) -------------------

    @property
    def cache(self) -> Any:
        if self.quantize_kv:
            # Sampled kv8 dequant cost (DESIGN.md §15); the fp path below
            # returns a reference and is not worth a timing window.
            return _obs_profile.sample_call(
                "kv.gather",
                lambda: dequantize_kv(self._qcache, str(self.dtype)),
                pool="stripe", path="cache",
            )
        return self._cache

    @cache.setter
    def cache(self, new: Any) -> None:
        if self.quantize_kv:
            self._qcache = _obs_profile.sample_call(
                "kv.scatter", lambda: quantize_kv(new),
                pool="stripe", path="cache",
            )
        else:
            self._cache = new

    # -- bookkeeping ---------------------------------------------------------

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_active(self) -> int:
        return self.n_slots - len(self._free)

    def occupancy(self) -> float:
        return self.n_active / self.n_slots

    def bytes_resident(self) -> int:
        """Device bytes held by the pool's *resident* cache form.

        Under kv8 that is the int8 value arrays plus their fp32 scale
        sidecars (the honest footprint of the quantized pool -- scales are
        real bytes); otherwise the fp pytree.  The pool is preallocated, so
        this is constant for the life of the pool: n_slots * max_len worth
        of state regardless of how many slots are live.
        """
        resident = self._qcache if self.quantize_kv else self._cache
        return sum(
            leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(resident)
        )

    def bytes_report(self) -> dict:
        """{"reserved": preallocated bytes (== ``bytes_resident``), "live":
        bytes actually valid under the pos mask}.

        ``reserved`` is the stripe the pool holds regardless of load --
        n_slots * max_len worth of state.  ``live`` counts, per slot,
        ``min(pos, seq_capacity)`` rows of every pos-masked attention leaf
        (a mid-prefill slot has host ``pos = -1`` and counts 0 until its
        final chunk lands -- exactly the rows the validity mask exposes) and
        the full per-slot block of maskless state leaves (SSM/hybrid state
        is dense once the slot is active).  The reserved/live gap is what
        the paged pool reclaims (DESIGN.md §13).
        """
        resident = self._qcache if self.quantize_kv else self._cache
        pos = np.maximum(self.positions, 0)
        active_frac = self.n_active / self.n_slots
        live = 0.0

        def nbytes(node) -> int:
            return sum(
                leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(node)
            )

        def walk(node) -> None:
            nonlocal live
            if (
                isinstance(node, dict)
                and "pos" in node
                and not isinstance(node["pos"], dict)
            ):
                cap = node["pos"].shape[2]
                frac = float(np.sum(np.minimum(pos, cap))) / float(
                    cap * self.n_slots
                )
                live += nbytes(node) * frac
            elif isinstance(node, dict):
                for v in node.values():
                    walk(v)
            elif isinstance(node, (list, tuple)):
                for v in node:
                    walk(v)
            else:
                live += nbytes(node) * active_frac

        walk(resident)
        return {"reserved": self.bytes_resident(), "live": int(round(live))}

    def active_slots(self) -> list[int]:
        free = set(self._free)
        return [s for s in range(self.n_slots) if s not in free]

    # -- slot lifecycle ------------------------------------------------------

    def alloc(self) -> int | None:
        """Claim a free slot (or None).  The slot stays masked (pos = -1)
        until ``write_prefill`` lands a request in it."""
        if not self._free:
            return None
        return self._free.pop()

    def free(self, slot: int) -> None:
        """Release a slot: mark every position -1 (old keys become
        unreachable under the masking rule) and zero the float state."""
        if slot in self._free or not 0 <= slot < self.n_slots:
            raise ValueError(f"free of invalid/already-free slot {slot}")
        self.positions[slot] = -1
        self.cache = clear_slots(
            self.cache, jnp.arange(self.n_slots) == slot, self.n_slots
        )
        self._free.append(slot)

    def write_prefill(self, slot: int, cache_one: Any, n_tokens: int) -> None:
        """Scatter a batch-1 primed cache (from ``model.prefill`` at this
        pool's max_len) into ``slot``; its next write position becomes
        ``n_tokens`` (prompt length incl. any non-text prefix)."""
        self.write_slot(slot, cache_one, next_pos=n_tokens)

    # -- chunked prefill: offset writes into one slot -------------------------

    def gather_slot(self, slot: int) -> Any:
        """Batch-1 view (copy) of ``slot`` -- the working cache a prefill
        chunk advances before ``write_slot`` puts it back."""
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"gather of invalid slot {slot}")
        with _obs_trace.span("kv.gather_slot", cat="kv", slot=slot):
            return _obs_profile.sample_call(
                "kv.gather",
                lambda: _gather_slot(self.cache, jnp.int32(slot)),
                pool="stripe", path="slot",
            )

    def write_slot(self, slot: int, cache_one: Any, next_pos: int | None) -> None:
        """Scatter a batch-1 cache back into ``slot``.

        ``next_pos`` set marks the slot live at that absolute position (end
        of prefill: the prompt length).  ``next_pos=None`` keeps the
        host-side position at -1 -- the mid-prefill state: the chunk's K/V
        rows are physically in the pool at their absolute offsets, but the
        decode step still sees the slot as empty (its query position is -1,
        every key masked, cache row untouched), so partially prefilled
        requests never contaminate co-scheduled decode steps.
        """
        shapes = jax.tree.map(lambda a: a.shape[1], cache_one)
        if any(s != 1 for s in jax.tree.leaves(shapes)):
            raise ValueError("write_slot expects a batch-1 cache")
        next_pos = check_next_pos(next_pos)

        def _scatter() -> Any:
            self.cache = _scatter_slot(self.cache, cache_one, jnp.int32(slot))
            return self._qcache if self.quantize_kv else self._cache

        with _obs_trace.span("kv.write_slot", cat="kv", slot=slot):
            _obs_profile.sample_call(
                "kv.scatter", _scatter, pool="stripe", path="slot"
            )
        if next_pos is not None:
            self.positions[slot] = next_pos

    # -- decode-step interface ----------------------------------------------

    def pos_vector(self) -> jax.Array:
        """(n_slots,) int32 per-slot positions for the vector-pos decode."""
        return jnp.asarray(self.positions, jnp.int32)

    def advance(self, slots) -> None:
        """One token decoded in each of ``slots``."""
        for s in slots:
            self.positions[s] += 1
