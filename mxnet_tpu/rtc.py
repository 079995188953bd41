"""Runtime-compiled user kernels (mx.rtc).

TPU-native redesign of the reference's NVRTC wrapper (include/mxnet/mxrtc.h,
src/common/mxrtc.cc, python/mxnet/rtc.py — SURVEY §2.1 #31): the reference
compiles user CUDA C strings to device kernels at runtime, cached by source.
The TPU-native analogue compiles user **Pallas** kernel source at runtime:
the user hands over Python source defining a function ``kernel(...)`` whose
parameters are input refs followed by output refs; we exec it, wrap it in
``pl.pallas_call`` (interpret mode off-TPU), jit, and cache by source hash —
the same cache-by-source discipline as MXRtc (mxrtc.h:26-40).

    rtc = mx.rtc.Rtc('axpy', ['x', 'y'], ['out'], '''
    def kernel(x_ref, y_ref, out_ref):
        out_ref[...] = x_ref[...] * 2.0 + y_ref[...]
    ''')
    rtc.push([x, y], [out])     # reference Rtc.push(ins, outs, grid, block)

Plain-jax fallback: source may instead define ``fn(*arrays) -> arrays`` and
be created with ``mode='jax'`` — runtime codegen without the kernel DSL.
"""
from __future__ import annotations

import hashlib
import textwrap
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from .base import MXNetError
from .ndarray import NDArray

_CACHE: Dict[str, "Rtc"] = {}


class Rtc:
    """A runtime-compiled kernel (reference python/mxnet/rtc.py Rtc).

    ``input_names``/``output_names`` document the signature; the compiled
    callable takes ``len(input_names)`` arrays and writes
    ``len(output_names)`` outputs whose shapes/dtypes are taken from the
    ``outputs`` NDArrays passed to :meth:`push` (the reference also sizes
    outputs from the bound NDArrays, mxrtc.h Push)."""

    def __init__(self, name: str, input_names: Sequence[str],
                 output_names: Sequence[str], src: str, mode: str = "pallas"):
        self.name = name
        self.input_names = list(input_names)
        self.output_names = list(output_names)
        self.src = textwrap.dedent(src)
        self.mode = mode
        if mode not in ("pallas", "jax"):
            raise MXNetError("rtc mode must be 'pallas' or 'jax'")
        ns: Dict = {"jnp": jnp, "jax": jax}
        if mode == "pallas":
            from jax.experimental import pallas as pl

            ns["pl"] = pl
        try:
            exec(compile(self.src, "<mx.rtc:%s>" % name, "exec"), ns)
        except Exception as e:
            raise MXNetError("rtc source failed to compile: %s" % e) from e
        entry = "kernel" if mode == "pallas" else "fn"
        if entry not in ns:
            raise MXNetError(
                "rtc source must define a function named %r" % entry)
        self._user_fn = ns[entry]
        self._compiled: Dict[Tuple, "jax.stages.Wrapped"] = {}

    def _get_compiled(self, out_specs):
        key = tuple(out_specs)
        fn = self._compiled.get(key)
        if fn is not None:
            return fn
        if self.mode == "pallas":
            from jax.experimental import pallas as pl

            from .ops.pallas import on_tpu

            user = self._user_fn
            call = pl.pallas_call(
                user,
                out_shape=[jax.ShapeDtypeStruct(s, d) for s, d in out_specs],
                interpret=not on_tpu(),
            )
            fn = jax.jit(lambda *ins: call(*ins))
        else:
            fn = jax.jit(self._user_fn)
        self._compiled[key] = fn
        return fn

    def push(self, ins: Sequence[NDArray], outs: Sequence[NDArray],
             grid_dims=None, block_dims=None):
        """Run the kernel (reference Rtc.push). ``grid_dims``/``block_dims``
        are accepted for API parity and ignored — grid/tiling on TPU comes
        from the kernel's own pallas grid spec, not a launch config."""
        if len(ins) != len(self.input_names):
            raise MXNetError("%s expects %d inputs, got %d"
                             % (self.name, len(self.input_names), len(ins)))
        if len(outs) != len(self.output_names):
            raise MXNetError("%s expects %d outputs, got %d"
                             % (self.name, len(self.output_names), len(outs)))
        out_specs = [(tuple(o.shape), o._data.dtype) for o in outs]
        fn = self._get_compiled(out_specs)
        results = fn(*[x._data for x in ins])
        if not isinstance(results, (list, tuple)):
            results = [results]
        for o, r in zip(outs, results):
            o._data = r
        return outs


def create(name: str, input_names, output_names, src: str,
           mode: str = "pallas") -> Rtc:
    """Compile (or fetch cached) — reference MXRtcCreate + source cache."""
    key = hashlib.sha1(
        ("%s|%s|%s" % (name, mode, src)).encode()).hexdigest()
    rtc = _CACHE.get(key)
    if rtc is None:
        rtc = Rtc(name, input_names, output_names, src, mode)
        _CACHE[key] = rtc
    return rtc
