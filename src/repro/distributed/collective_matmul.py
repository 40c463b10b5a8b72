"""Mesh-level systolic GEMM: shard_map tensor parallelism with overlapped
collectives (DESIGN.md §6).

The paper's third array dimension replicates dot-product layers until ~99% of
the chip's DSPs are busy; this module is the same replication argument one
level up -- replicate the whole per-chip systolic kernel across the "model"
axis of a mesh and keep every copy busy by hiding the inter-chip traffic
under compute.  Two sharded GEMM forms cover the transformer's projections:

  ``all_gather_matmul``      A row-sharded (M/tp, K), B column-sharded
                             (K, N/tp) -> Y column-sharded (M, N/tp).
                             Column-parallel up-projections and any
                             prefill/training GEMM whose activations are
                             sequence-sharded.
  ``reduce_scatter_matmul``  A column-sharded (M, K/tp), B row-sharded
                             (K/tp, N) -> Y row-sharded (M/tp, N).
                             Row-parallel down/out-projections, where each
                             shard holds a partial sum over its K slice.

Both decompose their collective into ``tp - 1`` ``lax.ppermute`` ring hops
pipelined against per-shard calls into the existing Pallas systolic kernel
(the *collective matmul* pattern, Wang et al.): at every step the next chunk
is already in flight while the current chunk multiplies, so each hop hides
under the previous block matmul.  ``overlap=False`` falls back to the
unoverlapped ``all_gather``-then-matmul / matmul-then-``psum_scatter``
forms, kept as the benchmark baseline (``benchmarks/tp_matmul.py``).

Numerics: the per-shard kernel accumulates fp32 exactly like the
single-device kernel; ``reduce_scatter_matmul`` carries its cross-shard
partial sums in fp32 and casts once at the end.  Outputs therefore match the
single-device systolic reference to fp32 round-off (the accumulation
*grouping* differs, so bit-equality is not guaranteed -- see
``tests/test_distributed.py``).

Block plans: the per-shard problem is (M/tp, N/tp, K) or (M/tp, N, K/tp) --
a *different* tuning problem per mesh shape, which is why the ``repro.tune``
cache key carries ``tp`` (schema v2).  Resolution order per call: explicit
``block`` argument > tp-keyed tune-cache entry for the global problem >
the per-shard dispatcher's own heuristic.

``tensor_parallel(mesh)`` is the opt-in context that makes
``repro.core.ops.matmul`` route eligible projections through this module
(DESIGN.md §3), so model code needs no changes to run TP.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.obs import metrics as _obs_metrics
from repro.obs import trace as _obs_trace

DIRECTIONS = ("plus", "minus")


def _hop_step_model(
    tp: int, m: int, n: int, k: int, dtype, hop_bytes: int
) -> tuple[float, float]:
    """(t_hop, t_step) under the chip model: one ring hop's transfer time
    and one ring step's shard-GEMM compute time."""
    from repro.core import hw

    chip = hw.get_chip(None)
    t_hop = hop_bytes / chip.ici_bw_per_link
    step_flops = 2.0 * (m // tp) * n * k / tp  # one ring step's shard GEMM
    t_step = step_flops / chip.peak_flops(str(dtype))
    return t_hop, t_step


def _record_dispatch(
    mode: str, tp: int, m: int, n: int, k: int, dtype, overlap: bool, hop_bytes: int
) -> None:
    """Telemetry for one sharded-GEMM dispatch (host side, trace time).

    Counts ring traffic and publishes the modelled hop/compute overlap ratio
    (t_hop / t_step under the chip model; < 1.0 means each hop hides under
    its block matmul -- the mesh-level balance condition of DESIGN.md §6).
    The gauge carries ``kind="modeled"`` so it can never be confused with
    the sampled ``kind="measured"`` series ``_record_measured`` writes.
    Per-hop "tp.ring_hop" spans are trace-time structural markers (the hops
    themselves run on-device inside shard_map), carrying bytes + modelled
    seconds in args.
    """
    if not _obs_metrics.enabled():
        return
    hops = tp - 1 if overlap else 0
    _obs_metrics.inc("collective.calls", mode=mode)
    _obs_metrics.inc("collective.hops", hops, mode=mode)
    _obs_metrics.inc("collective.hop_bytes", hop_bytes * hops, mode=mode)
    t_hop, t_step = _hop_step_model(tp, m, n, k, dtype, hop_bytes)
    ratio = t_hop / t_step if t_step > 0 else float("inf")
    _obs_metrics.set_gauge(
        "collective.overlap_ratio", ratio, mode=mode, kind="modeled"
    )
    for s in range(hops):
        with _obs_trace.span(
            "tp.ring_hop", cat="trace",
            mode=mode, hop=s, bytes=hop_bytes, modeled_s=t_hop,
        ):
            pass


def _record_measured(
    mode: str,
    tp: int,
    m: int,
    n: int,
    k: int,
    dtype,
    hop_bytes: int,
    wall_s: float,
) -> None:
    """Measured counterpart of the modeled overlap gauge.

    ``wall_s`` is a sampled dispatch-to-retire window around the whole
    sharded GEMM.  The chip model says the compute floor is ``tp`` ring
    steps of ``t_step`` each; whatever the wall clock shows beyond that is
    *exposed* (un-overlapped) communication, so the measured per-hop
    overlap ratio is ``exposed / hops / t_step`` — directly comparable to
    the modeled ``t_hop / t_step`` gauge, and like it, < 1.0 means hops
    (mostly) hid under their block matmuls.
    """
    if not _obs_metrics.enabled():
        return
    hops = tp - 1
    if hops <= 0:
        return
    _, t_step = _hop_step_model(tp, m, n, k, dtype, hop_bytes)
    if t_step <= 0:
        return
    exposed_per_hop = max(0.0, wall_s - tp * t_step) / hops
    ratio = exposed_per_hop / t_step
    _obs_metrics.set_gauge(
        "collective.overlap_ratio", ratio, mode=mode, kind="measured"
    )
    _obs_metrics.observe(
        "collective.wall_us", wall_s * 1e6, mode=mode, tp=tp
    )


# ---------------------------------------------------------------------------
# Tensor-parallel context (consulted by repro.core.ops.matmul)
# ---------------------------------------------------------------------------

_TP = contextvars.ContextVar("repro_tensor_parallel", default=None)


@contextlib.contextmanager
def tensor_parallel(mesh: Mesh, axis: str = "model"):
    """Route eligible ``core.ops.matmul`` calls through the sharded path.

    Inside this context every 2D-flattenable projection whose shapes divide
    the ``axis`` size runs as an overlapped ``all_gather_matmul`` over
    ``mesh``; everything else falls through to the single-device backend
    unchanged (divisibility is checked per call, never assumed).
    """
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no axis {axis!r}")
    token = _TP.set((mesh, axis))
    try:
        yield
    finally:
        _TP.reset(token)


def current_tensor_parallel() -> tuple[Mesh, str] | None:
    """The active (mesh, axis) pair, or None outside ``tensor_parallel``."""
    return _TP.get()


# ---------------------------------------------------------------------------
# Per-shard kernel call + plan resolution
# ---------------------------------------------------------------------------


def _tp_tuned_block(
    m, n, k, dtype, tp, shard_shape: tuple[int, int, int]
) -> tuple[int, int, int] | None:
    """tp-keyed tune-cache consultation for the *global* problem, clamped to
    the per-shard ring-step problem ``shard_shape`` the kernel actually runs
    (never raises; a miss means the per-shard dispatcher's heuristic
    decides).  Delegates to ``tune.cache.tuned_block`` so the key schema and
    clamp rule stay in one place."""
    try:
        from repro.core import hw
        from repro.tune import cache as tune_cache
    except ImportError:  # pragma: no cover
        return None
    return tune_cache.tuned_block(
        "pallas-systolic",
        hw.get_chip(None),
        m,
        n,
        k,
        dtype,
        tp=tp,
        clamp_to=shard_shape,
    )


def _local_matmul(x, w, *, out_dtype, block, interpret):
    """One per-shard call into the existing Pallas systolic kernel."""
    from repro.core.blocking import BlockPlan
    from repro.kernels.systolic import ops as systolic_ops

    plan = None
    if block is not None:
        plan = BlockPlan(
            x.shape[0], w.shape[1], x.shape[1], *block, in_dtype=str(x.dtype)
        )
    return systolic_ops.matmul(
        x, w, out_dtype=out_dtype, plan=plan, interpret=interpret
    )


def _ring_perm(tp: int, direction: str) -> list[tuple[int, int]]:
    if direction not in DIRECTIONS:
        raise ValueError(f"direction must be one of {DIRECTIONS}, got {direction!r}")
    step = 1 if direction == "plus" else -1
    return [(i, (i + step) % tp) for i in range(tp)]


def _check_divisible(name: str, dim: int, tp: int) -> None:
    if dim % tp:
        raise ValueError(
            f"{name}={dim} does not divide over tp={tp}; pad the problem or "
            f"drop to the single-device path"
        )


# ---------------------------------------------------------------------------
# All-gather matmul (column-parallel): A (M/tp, K) x B (K, N/tp) -> (M, N/tp)
# ---------------------------------------------------------------------------


def _ag_shard(a_blk, b_blk, *, axis, tp, direction, overlap,
              out_dtype, block, interpret):
    m_sh = a_blk.shape[0]
    if not overlap:
        a_full = lax.all_gather(a_blk, axis, axis=0, tiled=True)
        return _local_matmul(
            a_full, b_blk, out_dtype=out_dtype, block=block, interpret=interpret
        )
    idx = lax.axis_index(axis)
    perm = _ring_perm(tp, direction)
    # With perm i -> i+1 the chunk held after s hops originated at idx - s;
    # the opposite ring direction negates the offset.
    sign = -1 if direction == "plus" else 1
    out = jnp.zeros((m_sh * tp, b_blk.shape[1]), out_dtype)
    cur = a_blk
    for s in range(tp):
        src = (idx + sign * s) % tp
        # Issue the hop BEFORE the block matmul: both depend only on `cur`,
        # so the scheduler runs the transfer under the compute (the
        # collective-matmul overlap).  The last chunk needs no hop.
        nxt = lax.ppermute(cur, axis, perm) if s < tp - 1 else None
        blk = _local_matmul(
            cur, b_blk, out_dtype=out_dtype, block=block, interpret=interpret
        )
        out = lax.dynamic_update_slice(out, blk, (src * m_sh, 0))
        if nxt is not None:
            cur = nxt
    return out


def all_gather_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "model",
    out_dtype=None,
    direction: str = "plus",
    overlap: bool = True,
    block: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """(M, K) @ (K, N) with A row-sharded and B column-sharded over ``axis``.

    Returns the full (M, N) result, column-sharded ``P(None, axis)``.  The
    all-gather of A is decomposed into ``tp - 1`` ring ``ppermute`` hops,
    each hidden under the previous (M/tp, K) x (K, N/tp) block matmul.
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    tp = mesh.shape[axis]
    _check_divisible("M", m, tp)
    _check_divisible("N", n, tp)
    out_dtype = jnp.dtype(out_dtype or a.dtype)
    # Each hop moves one (M/tp, K) chunk of A at the input dtype.
    _record_dispatch(
        "allgather", tp, m, n, k, a.dtype, overlap,
        (m // tp) * k * a.dtype.itemsize,
    )
    if block is None:
        block = _tp_tuned_block(m, n, k, a.dtype, tp, (m // tp, n // tp, k))
    fn = functools.partial(
        _ag_shard,
        axis=axis,
        tp=tp,
        direction=direction,
        overlap=overlap,
        out_dtype=out_dtype,
        block=block,
        interpret=interpret,
    )
    sharded = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(axis, None), P(None, axis)),
        out_specs=P(None, axis),
        check_vma=False,  # pallas_call has no replication rule
    )
    if overlap and not isinstance(a, jax.core.Tracer):
        from repro.obs import profile as _obs_profile

        out, wall = _obs_profile.get_profiler().timed(
            "collective", lambda: sharded(a, b), mode="allgather", tp=tp
        )
        if wall is not None:
            _record_measured(
                "allgather", tp, m, n, k, a.dtype,
                (m // tp) * k * a.dtype.itemsize, wall,
            )
        return out
    return sharded(a, b)


# ---------------------------------------------------------------------------
# Reduce-scatter matmul (row-parallel): A (M, K/tp) x B (K/tp, N) -> (M/tp, N)
# ---------------------------------------------------------------------------


def _rs_shard(a_blk, b_blk, *, axis, tp, direction, overlap,
              out_dtype, block, interpret):
    m = a_blk.shape[0]
    m_sh = m // tp
    if not overlap:
        partial = _local_matmul(
            a_blk, b_blk, out_dtype=jnp.float32, block=block, interpret=interpret
        )
        return lax.psum_scatter(
            partial, axis, scatter_dimension=0, tiled=True
        ).astype(out_dtype)
    idx = lax.axis_index(axis)
    perm = _ring_perm(tp, direction)
    # Carry continuity (carry moves i -> i+1): at step s device idx adds its
    # partial for output chunk (idx - s - 1), so after tp steps the carry
    # arriving home holds all tp partials for the device's own chunk.
    sign = -1 if direction == "plus" else 1
    acc = None
    for s in range(tp):
        c = (idx + sign * (s + 1)) % tp
        rows = lax.dynamic_slice(a_blk, (c * m_sh, 0), (m_sh, a_blk.shape[1]))
        # fp32 partials: the cross-shard sum continues the kernel's own fp32
        # accumulation, casting to out_dtype exactly once at the end.
        partial = _local_matmul(
            rows, b_blk, out_dtype=jnp.float32, block=block, interpret=interpret
        )
        acc = partial if acc is None else acc + partial
        if s < tp - 1:
            acc = lax.ppermute(acc, axis, perm)
    return acc.astype(out_dtype)


def reduce_scatter_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "model",
    out_dtype=None,
    direction: str = "plus",
    overlap: bool = True,
    block: tuple[int, int, int] | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """(M, K) @ (K, N) with A column-sharded and B row-sharded over ``axis``.

    Each shard computes a partial product over its K slice; the cross-shard
    reduction + row scatter is decomposed into a ring of fp32 carries, one
    ``ppermute`` hop hidden under each (M/tp, K/tp) x (K/tp, N) block
    matmul.  Returns the full (M, N) result, row-sharded ``P(axis, None)``.
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    tp = mesh.shape[axis]
    _check_divisible("K", k, tp)
    _check_divisible("M", m, tp)
    out_dtype = jnp.dtype(out_dtype or a.dtype)
    # Each hop moves one (M/tp, N) fp32 partial-sum carry.
    _record_dispatch(
        "reducescatter", tp, m, n, k, a.dtype, overlap, (m // tp) * n * 4
    )
    if block is None:
        block = _tp_tuned_block(m, n, k, a.dtype, tp, (m // tp, n, k // tp))
    fn = functools.partial(
        _rs_shard,
        axis=axis,
        tp=tp,
        direction=direction,
        overlap=overlap,
        out_dtype=out_dtype,
        block=block,
        interpret=interpret,
    )
    sharded = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(None, axis), P(axis, None)),
        out_specs=P(axis, None),
        check_vma=False,  # pallas_call has no replication rule
    )
    if overlap and not isinstance(a, jax.core.Tracer):
        from repro.obs import profile as _obs_profile

        out, wall = _obs_profile.get_profiler().timed(
            "collective", lambda: sharded(a, b), mode="reducescatter", tp=tp
        )
        if wall is not None:
            _record_measured(
                "reducescatter", tp, m, n, k, a.dtype, (m // tp) * n * 4, wall
            )
        return out
    return sharded(a, b)


# ---------------------------------------------------------------------------
# Dispatch helpers
# ---------------------------------------------------------------------------

MODES = ("allgather", "reducescatter")


def tp_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    mesh: Mesh,
    mode: str = "allgather",
    **kw,
) -> jax.Array:
    """Mode-switched entry point (benchmarks / launchers)."""
    if mode == "allgather":
        return all_gather_matmul(a, b, mesh=mesh, **kw)
    if mode == "reducescatter":
        return reduce_scatter_matmul(a, b, mesh=mesh, **kw)
    raise ValueError(f"mode must be one of {MODES}, got {mode!r}")


def maybe_tp_matmul(x2: jax.Array, w: jax.Array, *, out_dtype) -> jax.Array | None:
    """The ``core.ops.matmul`` hook: sharded product or None.

    Returns None (caller falls through to its single-device backend) unless a
    ``tensor_parallel`` context is active with tp > 1 and the flattened
    (M, K) x (K, N) problem divides the mesh axis.  M >= tp keeps batch-1
    decode GEMMs (M < tp rows) on the replicated path where they belong.
    """
    active = _TP.get()
    if active is None:
        return None
    mesh, axis = active
    tp = mesh.shape[axis]
    m, n = x2.shape[0], w.shape[1]
    if tp < 2 or m < tp or m % tp or n % tp:
        return None
    return all_gather_matmul(x2, w, mesh=mesh, axis=axis, out_dtype=out_dtype)
