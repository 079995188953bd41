"""Multi-host distributed runtime.

Replaces the reference's ps-lite process fabric (SURVEY §2.1 #37, §3.4):
scheduler → jax.distributed coordinator; DMLC_ROLE/DMLC_PS_ROOT_URI env →
coordinator_address/process_id env; worker barrier →
multihost_utils.sync_global_devices; dead-node query
(kvstore_dist.h:159-168) → coordinator client health; tools/launch.py →
launch() helper spawning one process per host.

There are no separate 'server' processes: the optimizer state lives
sharded across the same mesh that computes (SURVEY §5.8 translation), so
every process is a worker.
"""
from __future__ import annotations

import os
import time
from typing import Optional

import jax


_initialized = False


def init(coordinator_address: Optional[str] = None,
         num_processes: Optional[int] = None,
         process_id: Optional[int] = None):
    """Initialize the multi-host runtime (idempotent).

    Resolution order: explicit args → MXNET_TPU_* env vars → resource-
    manager env (OpenMPI/MPICH `mpirun`, SLURM, SGE array tasks — the
    trackers the reference's dmlc launcher fed through DMLC_* env,
    reference tools/launch.py:33-60) → JAX auto-detection (TPU pod
    metadata). Single-process when nothing is configured — the same
    degradation as kvstore 'local' vs 'dist'."""
    global _initialized
    if _initialized:
        return
    coordinator_address = (coordinator_address
                           or os.environ.get("MXNET_TPU_COORDINATOR")
                           or None)  # empty string counts as unset
    if num_processes is None and os.environ.get("MXNET_TPU_NUM_PROCS"):
        num_processes = int(os.environ["MXNET_TPU_NUM_PROCS"])
    if process_id is None and os.environ.get("MXNET_TPU_PROC_ID"):
        process_id = int(os.environ["MXNET_TPU_PROC_ID"])
    if (coordinator_address is not None
            and (process_id is None or num_processes is None)):
        # resource-manager env only FILLS IN rank/world once a
        # coordinator is explicitly configured (launcher env or arg) —
        # RM variables alone must not promote a bare single-process run
        # to a distributed init that would block waiting for peers the
        # user never started (e.g. `python train.py` inside an sbatch
        # allocation without srun)
        rank_id, world = _resource_manager_rank()
        if process_id is None:
            process_id = rank_id
        if num_processes is None:
            num_processes = world
    if coordinator_address is None and num_processes in (None, 1):
        _initialized = True  # single-process mode
        return
    plats = (jax.config.jax_platforms
             or os.environ.get("JAX_PLATFORMS") or "")
    first = plats.split(",")[0].strip().lower()
    if first in ("", "cpu"):
        # multi-process CPU (the reference's multi-device-without-
        # hardware emulation, SURVEY §4.3, across OS processes): without
        # a CPU collectives backend each process builds a LOCAL-only
        # client and process_count() stays 1 — gloo makes the processes
        # form one global backend. Applied also when no platform is
        # configured (a CPU-only host resolves to cpu; on accelerator
        # hosts the option only affects the secondary CPU client). TPU
        # backends form the global view natively.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id)
    _initialized = True


def _resource_manager_rank():
    """(rank, world) from whatever resource manager launched this process:
    OpenMPI (OMPI_COMM_WORLD_*), MPICH/hydra (PMI_*), SLURM
    (SLURM_PROCID/SLURM_NTASKS), SGE array jobs (SGE_TASK_ID, 1-based).
    Returns (None, None) when none apply."""
    env = os.environ
    if "OMPI_COMM_WORLD_RANK" in env:
        return (int(env["OMPI_COMM_WORLD_RANK"]),
                int(env.get("OMPI_COMM_WORLD_SIZE", "1")))
    if "PMI_RANK" in env:
        return int(env["PMI_RANK"]), int(env.get("PMI_SIZE", "1"))
    if "SLURM_PROCID" in env:
        return (int(env["SLURM_PROCID"]),
                int(env.get("SLURM_NTASKS", "1")))
    if "SGE_TASK_ID" in env and env["SGE_TASK_ID"].isdigit():
        # array jobs may start anywhere and stride (qsub -t f-l:s):
        # rank = (id - first) / step, world = (last - first) / step + 1
        first = int(env.get("SGE_TASK_FIRST", "1"))
        step = int(env.get("SGE_TASK_STEPSIZE", "1") or "1")
        last = int(env.get("SGE_TASK_LAST", env["SGE_TASK_ID"]))
        return ((int(env["SGE_TASK_ID"]) - first) // step,
                (last - first) // step + 1)
    return None, None


def rank() -> int:
    """This process's rank (reference KVStore::get_rank, kvstore.h:227)."""
    return jax.process_index()


def size() -> int:
    """World size (reference KVStore::get_group_size, kvstore.h:232)."""
    return jax.process_count()


def barrier(name: str = "barrier"):
    """Global process barrier (reference Barrier → ps::Postoffice)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


def dead_nodes(step: Optional[int] = None) -> set:
    """Ranks currently considered dead — the poll surface the
    resilience.TrainingSupervisor consults between steps.

    Under jax.distributed a really-failed host aborts the job rather
    than running degraded, so live detection comes from the PS kvstore
    (``kv.num_dead_node`` / ``PSClient.dead_nodes``); what THIS function
    contributes is the simulated layer: ``kill_rank`` entries of the
    active ``MXNET_FAULT_PLAN`` (mxnet_tpu.resilience.faults) read as
    dead from their planned step on, through the same surface real
    deaths would use."""
    from ..resilience import faults  # lazy: resilience is optional depth

    return set(faults.killed_ranks(step))


def num_dead_nodes(timeout_s: float = 0.0) -> int:
    """Dead-node surface (reference MXKVStoreGetNumDeadNode,
    kvstore_dist.h:159-168). Under jax.distributed a failed host aborts
    the job rather than running degraded, so a live call sees only
    simulated deaths (:func:`dead_nodes`); the timeout is honored as a
    liveness probe window."""
    if timeout_s > 0 and not dead_nodes():
        deadline = time.time() + timeout_s
        while time.time() < deadline and not dead_nodes():
            time.sleep(min(0.1, deadline - time.time()))
    return len(dead_nodes())


def is_recovery() -> bool:
    """Recovery flag (reference ps::Postoffice::is_recovery). Restarted
    jobs resume from checkpoints (resilience.load_sharded /
    save_checkpoint) instead of rejoining live — always False."""
    return False
