"""ctypes binding + on-demand build of the C++ runtime (pe_runtime.cpp).

The reference ships one Makefile for its CUDA stage only
(``stage4-mpi+cuda/Makefile``) and builds stage0/1 ad hoc; here the
native library is built on first use with g++ (-O3 -fopenmp, falling
back to no-OpenMP if unavailable) and cached next to the source under a
name carrying the source's hash, so a library built from any other
version of the source (a stale, untracked copy) is never loaded. No
pybind11 in this environment — the C ABI + ctypes keeps the binding
dependency-free.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
from typing import NamedTuple, Optional

import numpy as np

from poisson_ellipse_tpu.models.problem import Problem

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "pe_runtime.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None


class NativeBuildError(RuntimeError):
    """The C++ runtime could not be built or loaded (g++ missing, build
    failure) — an environment problem, distinct from solver errors."""


class NativeResult(NamedTuple):
    w: np.ndarray
    iters: int
    diff: float
    converged: bool
    breakdown: bool


def _lib_path() -> str:
    """The library built from ``pe_runtime.cpp`` as it is now."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_DIR, f"libpe_runtime-{digest}.so")


def _build(lib_path: str) -> Optional[str]:
    """Compile the shared library; returns an error string on failure.

    Compiles to a process-unique temp name and os.rename()s onto the
    final path: rename is atomic, so a concurrent process never dlopens
    a half-written library (the in-module lock is process-local only).
    Libraries built from older sources are removed.
    """
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    # attempt order: drop -march=native (not every g++/arch accepts it)
    # and -fopenmp independently so losing one flag never costs the other
    attempts = (
        ["-march=native", "-fopenmp"],
        ["-fopenmp"],
        ["-march=native"],
        [],
    )
    for flags in attempts:
        cmd = [
            "g++",
            "-O3",
            "-std=c++17",
            "-shared",
            "-fPIC",
            *flags,
            _SRC,
            "-o",
            tmp,
        ]
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=120
            )
        except (OSError, subprocess.TimeoutExpired) as e:
            return f"g++ invocation failed: {e}"
        if proc.returncode == 0:
            os.replace(tmp, lib_path)
            for stale in glob.glob(os.path.join(_DIR, "libpe_runtime*.so")):
                if stale != lib_path:
                    os.unlink(stale)
            return None
        err = proc.stderr
    return f"g++ failed:\n{err}"


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        lib_path = _lib_path()
        if not os.path.exists(lib_path):
            _build_error = _build(lib_path)
            if _build_error is not None:
                return None
        lib = ctypes.CDLL(lib_path)
        d = ctypes.c_double
        dp = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
        lib.pe_solve.restype = ctypes.c_int
        lib.pe_solve.argtypes = [
            ctypes.c_int, ctypes.c_int, d, d, d, d, d, d, d,
            ctypes.c_int, ctypes.c_int, ctypes.c_int,
            dp, ctypes.POINTER(ctypes.c_int), ctypes.POINTER(d),
        ]
        lib.pe_assemble.restype = ctypes.c_int
        lib.pe_assemble.argtypes = [
            ctypes.c_int, ctypes.c_int, d, d, d, d, d, d, dp, dp, dp,
        ]
        lib.pe_num_threads.restype = ctypes.c_int
        lib.pe_num_threads.argtypes = []
        _lib = lib
        return _lib


def native_available() -> bool:
    """True if the C++ runtime could be built and loaded."""
    return _load() is not None


def build_error() -> Optional[str]:
    _load()
    return _build_error


def num_threads() -> int:
    lib = _load()
    if lib is None:
        raise NativeBuildError(f"native runtime unavailable: {_build_error}")
    return lib.pe_num_threads()


def solve_native(problem: Problem, threads: int = 0) -> NativeResult:
    """Full C++ PCG solve. threads=1 → stage0 analog; >1 → stage1 analog;
    0 → OpenMP default."""
    lib = _load()
    if lib is None:
        raise NativeBuildError(f"native runtime unavailable: {_build_error}")
    w = np.zeros(problem.node_shape, np.float64)
    iters = ctypes.c_int(0)
    diff = ctypes.c_double(0.0)
    status = lib.pe_solve(
        problem.M,
        problem.N,
        problem.a1,
        problem.b1,
        problem.a2,
        problem.b2,
        problem.f_val,
        problem.delta,
        -1.0 if problem.eps is None else problem.eps,
        -1 if problem.max_iter is None else problem.max_iter,
        1 if problem.norm == "weighted" else 0,
        threads,
        w.reshape(-1),
        ctypes.byref(iters),
        ctypes.byref(diff),
    )
    if status < 0:
        raise ValueError(f"pe_solve rejected arguments (status {status})")
    return NativeResult(
        w=w,
        iters=iters.value,
        diff=diff.value,
        converged=status == 0,
        breakdown=status == 2,
    )


def assemble_native(problem: Problem):
    """C++ assembly of (a, b, rhs) — golden cross-check for ops.assembly."""
    lib = _load()
    if lib is None:
        raise NativeBuildError(f"native runtime unavailable: {_build_error}")
    shape = problem.node_shape
    a = np.zeros(shape, np.float64)
    b = np.zeros(shape, np.float64)
    rhs = np.zeros(shape, np.float64)
    status = lib.pe_assemble(
        problem.M,
        problem.N,
        problem.a1,
        problem.b1,
        problem.a2,
        problem.b2,
        problem.f_val,
        -1.0 if problem.eps is None else problem.eps,
        a.reshape(-1),
        b.reshape(-1),
        rhs.reshape(-1),
    )
    if status != 0:
        raise ValueError(f"pe_assemble rejected arguments (status {status})")
    return a, b, rhs
