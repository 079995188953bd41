"""Device mesh construction.

Replaces the reference's Context/group2ctx device-placement machinery
(include/mxnet/base.h:116-207, graph_executor.cc AssignContext :245-334)
with jax.sharding.Mesh axes. A Context named a single device; a MeshConfig
names how the whole job's devices factor into parallelism axes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("data", "expert", "seq", "pipe", "model")

_tracing = threading.local()


@contextlib.contextmanager
def partitioned_over(mesh: Optional[Mesh]):
    """Entered by the executor around the trace of a graph that XLA's SPMD
    partitioner will split over ``mesh`` (batch on its "data" axis). Most
    ops need not know; an op the partitioner cannot split — a Mosaic
    kernel — reads :func:`partition_mesh` and splits itself with shard_map.
    Trace-time Python state only: nothing here reaches the program."""
    prev = getattr(_tracing, "mesh", None)
    _tracing.mesh = mesh if mesh is not None and mesh.size > 1 else None
    try:
        yield
    finally:
        _tracing.mesh = prev


def partition_mesh() -> Optional[Mesh]:
    """The mesh the graph being traced is partitioned over, or None."""
    return getattr(_tracing, "mesh", None)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Axis sizes for the canonical 5-axis mesh. Any axis may be 1.

    "expert" is a dedicated expert-parallel axis (parallel/moe.py); MoE
    experts are sharded over the combined (data, expert, seq) group, so EP
    is exercised even when the expert axis itself is size 1."""

    data: int = 1
    expert: int = 1
    seq: int = 1
    pipe: int = 1
    model: int = 1

    @property
    def size(self) -> int:
        return self.data * self.expert * self.seq * self.pipe * self.model

    def axis_sizes(self):
        return (self.data, self.expert, self.seq, self.pipe, self.model)


def make_mesh(config: MeshConfig, devices: Optional[Sequence] = None) -> Mesh:
    """Build the Mesh. Axis order puts "model" innermost so tensor-parallel
    collectives ride nearest-neighbor ICI links, and "data" outermost so
    gradient all-reduce spans the slowest links (DCN on multi-host) —
    the standard ICI-vs-DCN layout recipe."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if config.size != n:
        raise ValueError(
            "mesh config %s needs %d devices, have %d" % (config, config.size, n))
    arr = np.asarray(devices).reshape(config.axis_sizes())
    return Mesh(arr, AXES)


def auto_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    """Factor n devices into (data, expert, seq, pipe, model) greedily:
    split off 2s into model, then pipe, then seq, then expert, rest to
    data. Guarantees tp/pp/sp are exercised on n>=8 (the virtual-CPU test
    mesh) and the dedicated expert axis on n>=16; EP itself is exercised
    for any n>=2 because experts shard over (data, expert, seq)."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    sizes = {"data": 1, "expert": 1, "seq": 1, "pipe": 1, "model": 1}
    for axis in ("model", "pipe", "seq", "expert"):
        if n % 2 == 0 and n > 1:
            sizes[axis] *= 2
            n //= 2
    sizes["data"] = n
    cfg = MeshConfig(**sizes)
    return make_mesh(cfg, devices)


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
