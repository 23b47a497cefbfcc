"""Compilation caching: every request shape hits a warm executable.

A serving stack dies on cold starts twice: once per *process* (XLA
recompiles everything a fresh worker ever traces) and once per *shape*
(a new (M, N, lanes) request retraces and recompiles even in a warm
worker). Two layers here, one per failure mode:

- **Persistent XLA compilation cache** (:func:`enable_persistent_cache`)
  — JAX's on-disk cache with the min-compile-time gate dropped to zero,
  so every compiled solver (any engine) lands on disk and a restarted
  worker deserialises instead of recompiling. Every entry point (the
  harness CLI, ``chip_smoke.py``, ``benchmark/``) turns it on at start.
  It lives in ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else at
  the fixed ``<repo>/.jax_cache/`` — the path is part of the cache key,
  so it never moves.

- **In-process AOT warm pool** (:class:`WarmPool`) — bucketed
  ahead-of-time executables for the *batched* engines, keyed by
  ``(engine, grid-bucket, dtype, lane-bucket, norm)``. Request shapes
  are rounded up to the nearest bucket and **pad-and-mask embedded**:
  operands are zero-padded to the bucket's node grid, an interior mask
  pins every node outside the true problem to zero, and all
  size-dependent *numbers* (h1, h2, δ, the iteration cap) enter the
  executable as runtime scalars — so one ``jit(...).lower().compile()``
  per bucket serves every smaller request with **zero retrace, zero
  recompile** (the second request for a bucketed shape returns the same
  executable object; hit-count asserted in ``tests/test_batched.py``).
  Lane counts round up to powers of two; surplus lanes carry a zero RHS
  and exit on the breakdown guard after one iteration, then are cropped
  from the result.

  Embedding note: the masked arithmetic adds only ``×1``/``+0`` on the
  true interior and exact zeros outside, but XLA's reduction tiling
  over the *bucket* shape may group partial sums differently from the
  exact-shape solve — bucketed results are value-equivalent within the
  usual reordering ulps (the pallas-vs-xla contract), not bitwise, and
  iteration counts may differ by a step on ill-conditioned grids.

Every pool lookup emits a ``cache:hit`` / ``cache:miss`` trace event and
bumps the ``compile_cache_hits`` / ``compile_cache_misses`` counters
(``obs``), so serving dashboards see cold-start behaviour directly.
``python -m poisson_ellipse_tpu.harness warmup`` pre-fills both layers.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from poisson_ellipse_tpu.models.problem import Problem
from poisson_ellipse_tpu.obs import metrics as obs_metrics
from poisson_ellipse_tpu.obs import trace as obs_trace

# the checkout this package runs from: everything the program writes
# (compile cache, autotune registry, native library) stays inside it
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set — JAX reads it itself,
    so no other directory is set here; otherwise ``<repo>/.jax_cache/``.
    Drops the min-compile-time and min-size gates so even millisecond
    compiles persist — the solver zoo is many small computations. Call
    it before the first compile: JAX decides once per process whether
    the cache is in use.
    """
    path = os.environ.get(ENV_CACHE_DIR)
    if not path:
        path = DEFAULT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    obs_trace.event("cache:persistent-enabled", dir=path)
    return path


# -- shape bucketing ---------------------------------------------------------

# grid-dimension ladder: powers of two and their 1.5× midpoints — at
# most 2 buckets per octave bounds pad waste at ≤ 50% per dim while
# keeping the executable population logarithmic in served sizes
_MAX_DIM = 1 << 20


def _ladder():
    k = 3
    while (1 << k) <= _MAX_DIM:
        yield 1 << k
        yield 3 << (k - 1)
        k += 1


def bucket_dim(n: int) -> int:
    """Smallest ladder value ≥ n (cells per grid dimension)."""
    if n < 2:
        raise ValueError("need at least 2 cells per dimension")
    for v in _ladder():
        if v >= n:
            return v
    raise ValueError(f"dimension {n} exceeds the bucket ladder")


def grid_bucket(M: int, N: int) -> tuple[int, int]:
    """The (Mb, Nb) cell-count bucket an (M, N) request embeds into."""
    return bucket_dim(M), bucket_dim(N)


def lane_bucket(lanes: int) -> int:
    """Smallest power of two ≥ lanes."""
    if lanes < 1:
        raise ValueError("lanes must be >= 1")
    return 1 << (lanes - 1).bit_length()


def warm_affinity_key(M: int, N: int, norm: str = "weighted") -> tuple:
    """The compile-bucket affinity key a request of grid (M, N) lands
    in: ``(grid_bucket, norm)`` — exactly the key the serve scheduler's
    batch contexts (``serve.scheduler._ctxs``) and this pool's bucketed
    executables share. The fleet router (``fleet.router``) routes by it:
    a request sent to a replica already holding this key's live batch
    context runs on an executable that is ALREADY warm — zero retrace,
    zero recompile, no cold-start tax on the unlucky replica."""
    return (grid_bucket(M, N), norm)


# -- the AOT warm pool -------------------------------------------------------


@dataclass
class _Entry:
    """One bucketed executable plus the bucket geometry it serves."""

    compiled: object
    engine: str
    bucket: tuple[int, int]
    lanes: int
    dtype: str
    norm: str
    compile_s: float
    # HBM storage width of the lane fields ("" = storage == compute).
    # A storage component in the cache key is load-bearing: a bf16-
    # storage executable and a full-width one trace DIFFERENT programs
    # for the same shapes, and serving one for the other would silently
    # change the accuracy contract of every request in the bucket.
    storage: str = ""


@dataclass
class WarmPool:
    """AOT executables for the batched engines, keyed by bucket.

    One pool per process is the intended shape (:func:`warm_pool`); the
    class is separate so tests can build throwaway pools.
    """

    entries: dict = field(default_factory=dict)
    hits: int = 0
    misses: int = 0

    @staticmethod
    def key(engine: str, grid: tuple[int, int], dtype, lanes: int,
            norm: str = "weighted", storage_dtype=None):
        # the storage-dtype component ("" when storage == compute): a
        # narrow-storage executable is a DIFFERENT traced program with a
        # different accuracy contract — it must never be served for a
        # full-width request (or vice versa)
        from poisson_ellipse_tpu.ops.precision import resolve_storage_dtype

        st = resolve_storage_dtype(storage_dtype, dtype)
        storage = "" if st is None else jnp.dtype(st).name
        return (
            engine, grid_bucket(*grid), jnp.dtype(dtype).name,
            lane_bucket(lanes), norm, storage,
        )

    def warmup(self, engine: str, grid: tuple[int, int], dtype=jnp.float32,
               lanes: int = 1, norm: str = "weighted",
               storage_dtype=None) -> _Entry:
        """The bucket executable for (engine, grid, dtype, lanes, norm,
        storage), AOT-compiling on miss — the pool's single (and
        deliberate) ``lower().compile()`` site.

        Emits ``cache:hit``/``cache:miss`` and bumps the obs counters;
        a hit returns the *same executable object* as the miss that
        created it (asserted in tests — the no-recompile contract).

        ``engine="auto"`` resolves to ``batched`` — the only lane
        engine with retire-and-refill + storage support. The tuned-
        config consult on the serving path lives at the scheduler's
        batch contexts (``Scheduler._ctx_for`` applies the registry's
        per-shape chunk at warm-pool admission); the tuner never
        scores lane engines, so there is no per-shape lane-engine
        choice to consult here.
        """
        if engine == "auto":
            engine = "batched"
        key = self.key(engine, grid, dtype, lanes, norm, storage_dtype)
        entry = self.entries.get(key)
        _, bucket, dtype_name, lb, _, storage = key
        if entry is not None:
            self.hits += 1
            obs_metrics.counter("compile_cache_hits").inc()
            obs_trace.event(
                "cache:hit", engine=engine, bucket=list(bucket),
                lanes=lb, dtype=dtype_name,
            )
            return entry
        self.misses += 1
        obs_metrics.counter("compile_cache_misses").inc()
        t0 = time.perf_counter()
        compiled = _compile_bucket(engine, bucket, dtype, lb, norm,
                                   storage_dtype=storage_dtype)
        compile_s = time.perf_counter() - t0
        obs_trace.event(
            "cache:miss", engine=engine, bucket=list(bucket), lanes=lb,
            dtype=dtype_name, compile_s=round(compile_s, 4),
        )
        entry = _Entry(
            compiled=compiled, engine=engine, bucket=bucket, lanes=lb,
            dtype=dtype_name, norm=norm, compile_s=compile_s,
            storage=storage,
        )
        self.entries[key] = entry
        return entry

    def solve(self, problem: Problem, lanes: int, engine: str = "batched",
              dtype=jnp.float32, rhs=None):
        """Serve one request from the pool: embed, dispatch, crop.

        ``rhs`` optionally supplies the (lanes, M+1, N+1) stack (default:
        the problem's RHS tiled). Returns a per-lane
        :class:`~poisson_ellipse_tpu.batch.BatchedPCGResult` cropped to
        the request's true shape and lane count.
        """
        from poisson_ellipse_tpu.batch.batched_pcg import BatchedPCGResult

        entry = self.warmup(
            engine, (problem.M, problem.N), dtype, lanes, problem.norm
        )
        args = _embed(problem, lanes, entry, dtype, rhs)
        out = entry.compiled(*args)
        result = BatchedPCGResult(*out)
        g1, g2 = problem.M + 1, problem.N + 1
        return BatchedPCGResult(
            w=result.w[:lanes, :g1, :g2],
            iters=result.iters[:lanes],
            diff=result.diff[:lanes],
            converged=result.converged[:lanes],
            breakdown=result.breakdown[:lanes],
            quarantined=result.quarantined[:lanes],
        )


def _compile_bucket(engine: str, bucket: tuple[int, int], dtype, lanes: int,
                    norm: str, storage_dtype=None):
    """AOT-compile one bucket-generic batched solver.

    The traced function takes every size-dependent number (h1, h2, δ,
    iteration cap) as a runtime scalar and the interior mask as a
    runtime array, so the compiled executable is reusable for every
    (M ≤ Mb, N ≤ Nb, lanes ≤ Lb) request — shapes are the only
    compile-time facts.
    """
    from poisson_ellipse_tpu.batch import batched_pcg, batched_pipelined

    if engine == "batched":
        mod = batched_pcg
    elif engine == "batched-pipelined":
        mod = batched_pipelined
    else:
        raise ValueError(
            f"the warm pool serves the batched engines; got {engine!r}"
        )
    if storage_dtype is not None and engine != "batched":
        raise ValueError(
            "narrow-storage bucket executables cover the 'batched' "
            f"engine; got {engine!r}"
        )
    Mb, Nb = bucket
    proto = Problem(M=Mb, N=Nb, norm=norm)

    def run(a, b, rhs, mask, h1, h2, delta, limit):
        kw = (
            {"storage_dtype": storage_dtype}
            if storage_dtype is not None else {}
        )
        state = mod.init_state(proto, a, b, rhs, mask=mask, h1=h1, h2=h2,
                               **kw)
        state = mod.advance(
            proto, a, b, rhs, state, limit=limit, mask=mask, h1=h1, h2=h2,
            delta=delta, **kw,
        )
        return tuple(mod.result_of(state))

    shape2 = jax.ShapeDtypeStruct((Mb + 1, Nb + 1), jnp.dtype(dtype))
    shape3 = jax.ShapeDtypeStruct((lanes, Mb + 1, Nb + 1), jnp.dtype(dtype))
    scalar = jax.ShapeDtypeStruct((), jnp.dtype(dtype))
    # the deliberate AOT site (tpulint TPU010's aot-warmup-fns carve-out
    # names this function's callers): compile NOW, off the request path
    return jax.jit(run).lower(  # tpulint: disable=TPU004
        shape2, shape2, shape3, shape2, scalar, scalar, scalar,
        jax.ShapeDtypeStruct((), jnp.int32),
    ).compile()


def _embed(problem: Problem, lanes: int, entry: _Entry, dtype, rhs=None):
    """Pad-and-mask a request into ``entry``'s bucket: zero-padded
    operands, interior mask over the true problem, surplus lanes zero
    (they exit on the breakdown guard at iteration 1 and are cropped)."""
    from poisson_ellipse_tpu.ops import assembly

    Mb, Nb = entry.bucket
    Lb = entry.lanes
    np_dtype = assembly.numpy_dtype(dtype)
    a, b, r = assembly.assemble_numpy(problem)
    g1, g2 = problem.M + 1, problem.N + 1
    pad2 = ((0, Mb + 1 - g1), (0, Nb + 1 - g2))
    a_p = np.pad(a, pad2).astype(np_dtype)
    b_p = np.pad(b, pad2).astype(np_dtype)
    if rhs is None:
        rhs_p = np.broadcast_to(np.pad(r, pad2), (Lb, Mb + 1, Nb + 1))
        rhs_p = rhs_p.astype(np_dtype)
    else:
        rhs = np.asarray(rhs)
        if rhs.shape != (lanes, g1, g2):
            raise ValueError(
                f"rhs shape {rhs.shape} != {(lanes, g1, g2)}"
            )
        rhs_p = np.zeros((Lb, Mb + 1, Nb + 1), np_dtype)
        rhs_p[:lanes, :g1, :g2] = rhs
    mask = np.zeros((Mb + 1, Nb + 1), np_dtype)
    mask[1 : problem.M, 1 : problem.N] = 1.0
    return (
        jnp.asarray(a_p), jnp.asarray(b_p), jnp.asarray(rhs_p),
        jnp.asarray(mask),
        jnp.asarray(problem.h1, dtype), jnp.asarray(problem.h2, dtype),
        jnp.asarray(problem.delta, dtype),
        jnp.asarray(problem.max_iterations, jnp.int32),
    )


# -- the process-wide pool ---------------------------------------------------

_POOL: Optional[WarmPool] = None


def warm_pool() -> WarmPool:
    """The process's shared warm pool (created on first use)."""
    global _POOL
    if _POOL is None:
        _POOL = WarmPool()
    return _POOL
