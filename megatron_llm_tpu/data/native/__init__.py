"""ctypes loader for the native dataset index helpers.

Reference: megatron/data/dataset_utils.py:82 ``compile_helper`` — the
reference also builds its C++ helper lazily at first use (via make).  The
Python callers keep vectorized numpy fallbacks, so the native library is an
optimization, never a requirement.

The binary is keyed by the source: ``_helpers_<sha256 of helpers.cpp>.so``.
``*.so`` is git-ignored, so a copied working tree may carry a binary no
commit describes; one built from another ``helpers.cpp`` is never loaded.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from typing import Optional, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(_DIR, "helpers.cpp"), "rb") as _src:
    _SO = os.path.join(
        _DIR, f"_helpers_{hashlib.sha256(_src.read()).hexdigest()[:16]}.so")
_lib: Optional[ctypes.CDLL] = None
_load_attempted = False


def _compile() -> bool:
    try:
        subprocess.run(["make", "-C", _DIR, os.path.basename(_SO)],
                       check=True, capture_output=True, timeout=120)
        return os.path.isfile(_SO)
    except (subprocess.SubprocessError, OSError):
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    if _lib is not None or _load_attempted:
        return _lib
    _load_attempted = True
    if not os.path.isfile(_SO) and not _compile():
        return None
    try:
        lib = ctypes.CDLL(_SO)
    except OSError:
        return None
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    lib.build_sample_idx.argtypes = [
        i32p, i32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, i32p,
    ]
    lib.build_sample_idx.restype = ctypes.c_int
    lib.build_blending_indices.argtypes = [
        np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
        np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS"),
        ctypes.c_int32, ctypes.c_int64,
    ]
    lib.build_blending_indices.restype = None
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def build_sample_idx(sizes: np.ndarray, doc_idx: np.ndarray,
                     seq_length: int, num_samples: int) -> Optional[np.ndarray]:
    """Native sample-boundary map; None if the library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    sizes = np.ascontiguousarray(sizes, np.int32)
    doc_idx = np.ascontiguousarray(doc_idx, np.int32)
    out = np.empty((num_samples + 1, 2), np.int32)
    rc = lib.build_sample_idx(sizes, doc_idx, len(doc_idx),
                              seq_length, num_samples, out.reshape(-1))
    if rc != 0:
        raise AssertionError(
            f"not enough tokens for {num_samples} samples of "
            f"seq_length {seq_length}")
    return out


def build_blending_indices(
    weights: np.ndarray, size: int
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Native blend assignment; None if the library is unavailable."""
    lib = _load()
    if lib is None:
        return None
    weights = np.ascontiguousarray(weights, np.float64)
    assert len(weights) <= 256, "at most 256 datasets in a blend"
    dataset_index = np.empty(size, np.uint8)
    dataset_sample_index = np.empty(size, np.int64)
    lib.build_blending_indices(dataset_index, dataset_sample_index, weights,
                               len(weights), size)
    return dataset_index, dataset_sample_index
