"""Base utilities: errors, dtype registry, attribute parsing.

TPU-native analogue of the reference's `python/mxnet/base.py` +
`include/mxnet/base.h`. There is no C ABI here: the "library" is JAX/XLA, so
this module only carries the shared small pieces (error type, dtype codes,
string-attr coercion used for reference-compatible kwargs).

Reference: python/mxnet/base.py:41-108 (lib loading / MXNetError),
include/mxnet/base.h:86-90 (version).
"""
from __future__ import annotations

import ast
import os
from typing import Any

import numpy as np

__version__ = "0.9.5-tpu.1"

# Integer dtype codes match the reference's mshadow enum so that saved-param
# blobs are interchangeable (reference: python/mxnet/ndarray.py _DTYPE_NP_TO_MX).
_DTYPE_NP_TO_MX = {
    np.dtype(np.float32): 0,
    np.dtype(np.float64): 1,
    np.dtype(np.float16): 2,
    np.dtype(np.uint8): 3,
    np.dtype(np.int32): 4,
}
_DTYPE_MX_TO_NP = {v: k for k, v in _DTYPE_NP_TO_MX.items()}
# TPU-native extensions (codes outside the reference range).
try:  # bfloat16 is the TPU-native compute dtype
    import ml_dtypes

    _DTYPE_NP_TO_MX[np.dtype(ml_dtypes.bfloat16)] = 16
    _DTYPE_MX_TO_NP[16] = np.dtype(ml_dtypes.bfloat16)
    bfloat16 = np.dtype(ml_dtypes.bfloat16)
except ImportError:  # pragma: no cover
    bfloat16 = None
_DTYPE_NP_TO_MX[np.dtype(np.int64)] = 17
_DTYPE_MX_TO_NP[17] = np.dtype(np.int64)
_DTYPE_NP_TO_MX[np.dtype(np.bool_)] = 18
_DTYPE_MX_TO_NP[18] = np.dtype(np.bool_)


class MXNetError(RuntimeError):
    """Error raised by the framework (reference: python/mxnet/base.py:71)."""


#: JAX's persistent compilation cache when the environment names none: a
#: fixed path inside the checkout. The path is part of the cache's key, so
#: it never holds a pid, a time or a temporary directory.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def init_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.
    An entry point (chip_smoke.py, tools/probe_fit_step.py) calls this once,
    before the first compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX reads it and nothing is set in code; otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`. (The separate ``mxnet_tpu.progcache`` of
    serialized executables is unrelated and stays off by default.)"""
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if d:
        return d
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def dtype_np_to_mx(dtype) -> int:
    return _DTYPE_NP_TO_MX[np.dtype(dtype)]


def dtype_mx_to_np(code: int) -> np.dtype:
    return _DTYPE_MX_TO_NP[code]


def string_types():
    return (str,)


def coerce_attr(value: Any) -> Any:
    """Coerce a reference-style string attribute ("(2,2)", "true", "0.9")
    into a Python value. The reference parses kwargs through dmlc::Parameter
    string fields (SURVEY §5.6); we accept both native Python values and their
    string forms for drop-in compatibility.
    """
    if not isinstance(value, str):
        return value
    s = value.strip()
    low = s.lower()
    if low in ("true", "false"):
        return low == "true"
    if low in ("none", "null"):
        return None
    try:
        return ast.literal_eval(s)
    except (ValueError, SyntaxError):
        return value


def attrs_key(attrs: dict) -> tuple:
    """Hashable, deterministic key for an attrs dict (for jit caches)."""

    def freeze(v):
        if isinstance(v, dict):
            return tuple(sorted((k, freeze(x)) for k, x in v.items()))
        if isinstance(v, (list, tuple)):
            return tuple(freeze(x) for x in v)
        if isinstance(v, np.ndarray):
            return (v.dtype.str, v.shape, v.tobytes())
        return v

    return tuple(sorted((k, freeze(v)) for k, v in attrs.items()))
