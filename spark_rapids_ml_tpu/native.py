#
# ctypes surface over the in-tree C++ component (native/ — the reference's
# JNI loader analog, jvm/.../JniRAPIDSML.java:64-77: extract + System.load).
# Builds lazily with CMake on first use; all callers degrade gracefully when
# no toolchain is present (the JAX path never needs the native lib — it exists
# for native-stack parity: covariance accumulation, symmetric eig, signflip).
#
from __future__ import annotations

import ctypes
import os
import shutil
import subprocess
from typing import Optional, Tuple

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_BUILD_DIR = os.path.join(_NATIVE_DIR, "build")
_LIB: Optional[ctypes.CDLL] = None
_LOAD_ERROR: Optional[str] = None


def _lib_path() -> str:
    return os.path.join(_BUILD_DIR, "libsrml_native.so")


def build(force: bool = False) -> str:
    """Build libsrml_native.so with CMake (reference jvm/native build step)."""
    if os.path.exists(_lib_path()) and not force:
        return _lib_path()
    # configure FRESH from the committed native/ sources: a build directory
    # left behind by another checkout carries a CMakeCache.txt with that
    # checkout's paths, which CMake refuses to reuse
    shutil.rmtree(_BUILD_DIR, ignore_errors=True)
    subprocess.run(
        ["cmake", "-S", _NATIVE_DIR, "-B", _BUILD_DIR, "-DCMAKE_BUILD_TYPE=Release"],
        check=True, capture_output=True,
    )
    subprocess.run(
        ["cmake", "--build", _BUILD_DIR, "--parallel"],
        check=True, capture_output=True,
    )
    return _lib_path()


def load(auto_build: bool = True) -> ctypes.CDLL:
    """Load (building if needed) the native library; raises RuntimeError with
    the underlying cause when unavailable."""
    global _LIB, _LOAD_ERROR
    if _LIB is not None:
        return _LIB
    if _LOAD_ERROR is not None:
        raise RuntimeError(f"native library unavailable: {_LOAD_ERROR}")
    try:
        path = _lib_path()
        if not os.path.exists(path):
            if not auto_build:
                raise FileNotFoundError(path)
            build()
        lib = ctypes.CDLL(path)
        lib.srml_cov_accumulate.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_double),
        ]
        lib.srml_weighted_mean.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double),
            ctypes.c_int64, ctypes.c_int64, ctypes.POINTER(ctypes.c_double),
        ]
        lib.srml_eigh_jacobi.restype = ctypes.c_int
        lib.srml_eigh_jacobi.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_double),
            ctypes.c_int, ctypes.c_double,
        ]
        lib.srml_signflip.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.c_int64, ctypes.c_int64,
        ]
        _LIB = lib
        return lib
    except Exception as e:  # record so later callers fail fast with the cause
        _LOAD_ERROR = str(e)
        raise RuntimeError(f"native library unavailable: {_LOAD_ERROR}") from e


def available() -> bool:
    try:
        load()
        return True
    except RuntimeError:
        return False


def _dptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_double))


def cov_accumulate(x: np.ndarray, c: Optional[np.ndarray] = None) -> np.ndarray:
    """C += XᵀX (row-major blocked; rapidsml_jni dgemmCov analog)."""
    lib = load()
    x = np.ascontiguousarray(x, dtype=np.float64)
    n, d = x.shape
    if c is None:
        c = np.zeros((d, d), dtype=np.float64)
    else:
        c = np.ascontiguousarray(c, dtype=np.float64)
    lib.srml_cov_accumulate(_dptr(x), n, d, _dptr(c))
    return c


def weighted_mean(x: np.ndarray, w: Optional[np.ndarray] = None) -> np.ndarray:
    lib = load()
    x = np.ascontiguousarray(x, dtype=np.float64)
    n, d = x.shape
    out = np.zeros(d, dtype=np.float64)
    wp = _dptr(np.ascontiguousarray(w, dtype=np.float64)) if w is not None else None
    lib.srml_weighted_mean(_dptr(x), wp, n, d, _dptr(out))
    return out


def eigh(a: np.ndarray, max_sweeps: int = 60, tol: float = 1e-14) -> Tuple[np.ndarray, np.ndarray]:
    """Symmetric eigendecomposition (cyclic Jacobi): ascending eigenvalues,
    eigenvectors as COLUMNS (numpy.linalg.eigh convention; the reference's
    cuSOLVER eigDC analog, rapidsml_jni.cu:215-269)."""
    lib = load()
    a = np.ascontiguousarray(a, dtype=np.float64)
    d = a.shape[0]
    if a.shape != (d, d):
        raise ValueError("eigh expects a square matrix")
    evals = np.zeros(d, dtype=np.float64)
    evecs = np.zeros((d, d), dtype=np.float64)
    rc = lib.srml_eigh_jacobi(_dptr(a), d, _dptr(evals), _dptr(evecs), max_sweeps, tol)
    if rc < 0:
        raise RuntimeError("Jacobi eigensolver did not converge")
    return evals, evecs


def signflip(comps: np.ndarray) -> np.ndarray:
    """Row-wise sign canonicalization (rapidsml_jni.cu:35-61 semantics)."""
    lib = load()
    comps = np.ascontiguousarray(comps, dtype=np.float64)
    k, d = comps.shape
    lib.srml_signflip(_dptr(comps), k, d)
    return comps


def pca_from_cov(
    x: np.ndarray, k: int, w: Optional[np.ndarray] = None
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """End-to-end native PCA fit on host data: mean -> centered covariance ->
    Jacobi eig -> top-k sign-flipped components. Mirrors the Scala path
    RapidsRowMatrix.computePrincipalComponentsAndExplainedVariance
    (RapidsRowMatrix.scala:59-141). Returns (components [k, d], explained
    variance [k], mean [d])."""
    x = np.ascontiguousarray(x, dtype=np.float64)
    n, d = x.shape
    mean = weighted_mean(x, w)
    xc = x - mean[None, :]
    if w is not None:
        xc = xc * np.sqrt(np.asarray(w, dtype=np.float64))[:, None]
        denom = float(np.sum(w)) - 1.0
    else:
        denom = float(n) - 1.0
    cov = cov_accumulate(xc) / max(denom, 1.0)
    evals, evecs = eigh(cov)
    top = np.argsort(evals)[::-1][:k]
    comps = signflip(evecs[:, top].T.copy())
    var = evals[top]
    return comps, var, mean
