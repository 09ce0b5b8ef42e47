"""Launcher + elastic supervisor.

TPU-native equivalent of the reference's process manager (upstream layout:
python/paddle/distributed/launch/ — ``Context``/``CollectiveController``
spawning per-device ``Container`` subprocesses with PADDLE_TRAINER_* env,
watching and restarting them; elastic manager at fleet/elastic/manager.py).

Differences by design:

  * one process per **host** (a jax process drives every local TPU chip),
    not one per device — ``--nprocs`` exists for CPU-backend testing and
    multi-host emulation on one machine;
  * rendezvous is jax's coordination service: the launcher only picks the
    coordinator address and exports ``COORDINATOR_ADDRESS`` /
    ``NUM_PROCESSES`` / ``PROCESS_ID`` (the same role as the reference's
    PADDLE_MASTER / PADDLE_TRAINERS_NUM / PADDLE_TRAINER_ID), which
    ``init_parallel_env`` consumes;
  * elastic supervision is a restart-from-checkpoint loop (the reference's
    ElasticManager watches etcd and rewrites endpoints; jax's coordination
    service cannot survive member loss, so the recovery unit is the whole
    job): any worker death tears the group down and respawns it with a
    fresh coordinator port and ``PADDLE_TPU_RESTART_NUM`` incremented —
    training scripts resume from their latest checkpoint.
"""

from __future__ import annotations

import dataclasses
import os
import signal
import socket
import subprocess
import sys
import time
from typing import List, Optional, Sequence

__all__ = ["LaunchConfig", "launch", "elastic_run", "find_free_port"]


def find_free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclasses.dataclass
class LaunchConfig:
    nprocs: int = 1
    master: Optional[str] = None      # host:port; default = local free port
    backend: str = "tpu"              # "tpu" | "cpu" (gloo collectives)
    max_restarts: int = 0             # elastic: restarts after worker death
    log_dir: Optional[str] = None     # per-worker logs; None = inherit stdio
    devices_per_proc: Optional[int] = None  # cpu backend: fake device count
    monitor_interval: float = 0.5
    # Topology-elastic restart (SURVEY §7 hard part (d), the reference's
    # ElasticManager scale-in/out): restart_nprocs[k-1] is the world size
    # for restart incarnation k — e.g. nprocs=2, restart_nprocs=[1] models
    # losing a host and resuming on the survivor.  Training scripts need no
    # special handling beyond checkpoint/resume: load_state_dict reshards
    # to whatever mesh the new incarnation builds.
    restart_nprocs: Optional[Sequence[int]] = None


class _Worker:
    def __init__(self, proc: subprocess.Popen, rank: int, log):
        self.proc = proc
        self.rank = rank
        self.log = log


def _spawn(cmd: Sequence[str], cfg: LaunchConfig, coordinator: str,
           restart_num: int, nprocs: Optional[int] = None) -> List[_Worker]:
    nprocs = nprocs if nprocs is not None else cfg.nprocs
    if cfg.backend == "tpu" and nprocs > 1:
        raise ValueError(
            f"backend='tpu' with nprocs={nprocs}: a chip belongs to one "
            f"process at a time and a jax process takes every chip of its "
            f"host, so {nprocs} local workers would fail or hang on each "
            f"other's chips.  Run one process per host (nprocs=1 here, one "
            f"launcher per host with --master), or backend='cpu' for "
            f"multi-process emulation on one machine.")
    workers = []
    for rank in range(nprocs):
        env = dict(os.environ)
        env.update({
            "COORDINATOR_ADDRESS": coordinator,
            "NUM_PROCESSES": str(nprocs),
            "PROCESS_ID": str(rank),
            "PADDLE_TPU_RESTART_NUM": str(restart_num),
            # reference-parity aliases
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(nprocs),
        })
        if cfg.backend == "cpu":
            env["PADDLE_TPU_BACKEND"] = "cpu"
            if cfg.devices_per_proc:
                # replace any inherited device-count flag (e.g. the test
                # conftest's 8) — duplicate XLA flags are unreliable
                flags = [f for f in env.get("XLA_FLAGS", "").split()
                         if not f.startswith(
                             "--xla_force_host_platform_device_count")]
                flags.append("--xla_force_host_platform_device_count="
                             + str(cfg.devices_per_proc))
                env["XLA_FLAGS"] = " ".join(flags)
        log = None
        if cfg.log_dir:
            os.makedirs(cfg.log_dir, exist_ok=True)
            log = open(os.path.join(
                cfg.log_dir, f"worker{rank}.r{restart_num}.log"), "w")
        proc = subprocess.Popen(
            list(cmd), env=env, stdout=log or None,
            stderr=subprocess.STDOUT if log else None)
        workers.append(_Worker(proc, rank, log))
    return workers


def _teardown(workers: List[_Worker], grace: float = 5.0):
    for w in workers:
        if w.proc.poll() is None:
            w.proc.send_signal(signal.SIGTERM)
    deadline = time.time() + grace
    for w in workers:
        timeout = max(0.1, deadline - time.time())
        try:
            w.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            w.proc.kill()
            w.proc.wait()
    for w in workers:
        if w.log:
            w.log.close()


def elastic_run(cmd: Sequence[str], cfg: LaunchConfig) -> int:
    """Run ``cmd`` as ``cfg.nprocs`` coordinated workers; supervise and
    restart the whole group (fresh rendezvous) on failure.

    Returns the final exit code (0 = a full group completed)."""
    restart_num = 0
    while True:
        nprocs = cfg.nprocs
        if restart_num > 0 and cfg.restart_nprocs:
            # elastic topology change: incarnation k runs at the declared
            # world size (clamped to the last entry once the list runs out)
            idx = min(restart_num - 1, len(cfg.restart_nprocs) - 1)
            nprocs = cfg.restart_nprocs[idx]
        coordinator = cfg.master or f"127.0.0.1:{find_free_port()}"
        workers = _spawn(cmd, cfg, coordinator, restart_num, nprocs)
        failed: Optional[int] = None
        try:
            while True:
                alive = False
                for w in workers:
                    rc = w.proc.poll()
                    if rc is None:
                        alive = True
                    elif rc != 0:
                        failed = rc
                        break
                if failed is not None or not alive:
                    break
                time.sleep(cfg.monitor_interval)
        finally:
            _teardown(workers)
        if failed is None:
            return 0
        if restart_num >= cfg.max_restarts:
            return failed
        restart_num += 1
        print(f"[paddle_tpu.launch] worker died (rc={failed}); "
              f"restart {restart_num}/{cfg.max_restarts}", file=sys.stderr)


def launch(script: str, script_args: Sequence[str] = (),
           cfg: Optional[LaunchConfig] = None) -> int:
    cfg = cfg or LaunchConfig()
    cmd = [sys.executable, "-u", script, *script_args]
    return elastic_run(cmd, cfg)
