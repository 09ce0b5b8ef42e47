"""Distributed environment bootstrap.

TPU-native equivalent of the reference's ``init_parallel_env`` path
(upstream layout: python/paddle/distributed/parallel.py → C++ TCPStore at
paddle/phi/core/distributed/store/tcp_store.cc → ProcessGroupNCCL creation).
The whole rendezvous dance (TCP store, ncclGetUniqueId exchange, per-ring
communicators) collapses into ``jax.distributed.initialize`` — jax's
coordination service IS the TCP store, and XLA owns all communicators.

What remains framework-level state is the **global hybrid topology**: one
:class:`~paddle_tpu.distributed.topology.HybridCommunicateGroup` installed
here and read by fleet, the collectives' default group, sharded layers, and
the parallelised train step.
"""

from __future__ import annotations

import os
from typing import Optional

from .topology import HybridCommunicateGroup

__all__ = [
    "init_parallel_env", "hybrid_group", "set_hybrid_group", "get_rank",
    "get_world_size", "is_initialized", "ParallelEnv",
]

_HCG: Optional[HybridCommunicateGroup] = None
_MULTIHOST_INITIALIZED = False
_ACTIVE_MESH = None  # sub-mesh override (pipeline stages)


import contextlib


@contextlib.contextmanager
def use_mesh(mesh):
    """Temporarily override the mesh that sharding constraints resolve
    against — pipeline stages trace their programs over a pp-less sub-mesh
    while the global topology still has the pp axis."""
    global _ACTIVE_MESH
    prev = _ACTIVE_MESH
    _ACTIVE_MESH = mesh
    try:
        yield mesh
    finally:
        _ACTIVE_MESH = prev


def active_mesh():
    """The mesh for sharding constraints: the use_mesh override, else the
    global hybrid mesh, else None."""
    if _ACTIVE_MESH is not None:
        return _ACTIVE_MESH
    return _HCG.mesh if _HCG is not None else None


def init_parallel_env(dp_degree: Optional[int] = None, mp_degree: int = 1,
                      pp_degree: int = 1, sharding_degree: int = 1,
                      sep_degree: int = 1,
                      coordinator_address: Optional[str] = None,
                      num_processes: Optional[int] = None,
                      process_id: Optional[int] = None
                      ) -> HybridCommunicateGroup:
    """Initialise distributed state and install the global topology.

    Single-process multi-device (one host driving a whole TPU slice) needs no
    rendezvous at all.  Multi-process (multi-host pods) goes through jax's
    coordination service; the connection parameters come from arguments or
    the standard env vars (``COORDINATOR_ADDRESS``/``NUM_PROCESSES``/
    ``PROCESS_ID``, which our launcher sets the way the reference's launcher
    sets PADDLE_MASTER/PADDLE_TRAINERS_NUM/PADDLE_TRAINER_ID).

    ``dp_degree=None`` means "whatever is left over" after the other axes.
    """
    global _HCG, _MULTIHOST_INITIALIZED
    import jax

    coord = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    if coord and not _MULTIHOST_INITIALIZED:
        if os.environ.get("PADDLE_TPU_BACKEND") == "cpu":
            # launcher --backend cpu (tests / multi-host emulation): pin the
            # CPU platform through the config API (it wins over whatever
            # JAX_PLATFORMS says) and use gloo for cross-process collectives
            jax.config.update("jax_platforms", "cpu")
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=num_processes or int(os.environ["NUM_PROCESSES"]),
            process_id=process_id or int(os.environ["PROCESS_ID"]))
        _MULTIHOST_INITIALIZED = True

    n = len(jax.devices())
    fixed = mp_degree * pp_degree * sharding_degree * sep_degree
    if dp_degree is None:
        if n % fixed:
            raise ValueError(f"device count {n} not divisible by "
                             f"mp*pp*sharding*sep = {fixed}")
        dp_degree = n // fixed
    _HCG = HybridCommunicateGroup(
        dp_degree=dp_degree, mp_degree=mp_degree, pp_degree=pp_degree,
        sharding_degree=sharding_degree, sep_degree=sep_degree)
    return _HCG


def set_hybrid_group(hcg: Optional[HybridCommunicateGroup]):
    global _HCG
    _HCG = hcg
    return hcg


def hybrid_group() -> Optional[HybridCommunicateGroup]:
    return _HCG


def is_initialized() -> bool:
    return _HCG is not None


def get_rank() -> int:
    """Process rank (parity: paddle.distributed.get_rank — but note one jax
    process drives many devices, where the reference runs one process per GPU)."""
    import jax
    return jax.process_index()


def get_world_size() -> int:
    import jax
    return jax.process_count()


class ParallelEnv:
    """Env-var view (parity: the reference's ParallelEnv reading
    PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM)."""

    @property
    def rank(self) -> int:
        return get_rank()

    @property
    def world_size(self) -> int:
        return get_world_size()

    @property
    def device_count(self) -> int:
        import jax
        return len(jax.local_devices())
