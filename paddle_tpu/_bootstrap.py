"""Early multi-process bootstrap: TCPStore rendezvous + jax.distributed.initialize.

Lives outside the `distributed` package so `paddle_tpu/__init__` can run it before
importing anything that touches the XLA backend (jax.distributed.initialize must be
the first backend-affecting call in the process). Reference flow:
python/paddle/distributed/parallel.py:978 init_parallel_env — TCPStore
(parallel.py:1134) then process-group creation; here the "process group" is JAX's
coordination service + GSPMD over the global device set.
"""
from __future__ import annotations

import os

import jax

_DONE = [False]
# the store created during early bootstrap; paddle_tpu.distributed.store's
# create_or_get_global_tcp_store() returns this same instance (a second master
# would fail to bind the already-listening rendezvous port)
_STORE = [None]


def early_init_distributed():
    """Idempotent; no-op unless the launcher env marks a multi-process run."""
    if _DONE[0]:
        return
    if os.environ.get("PADDLE_PSERVERS_IP_PORT_LIST"):
        # parameter-server mode: processes talk through the PS service
        # (distributed/ps), not through a collective jax.distributed world.
        # Matches role_maker's PS contract, where a missing TRAINING_ROLE
        # defaults to TRAINER. NOT latched (_DONE stays False): a later
        # explicit collective bootstrap in the same process still works.
        return
    world = _world_size_from_env()
    if world <= 1:
        _DONE[0] = True
        return
    # normalize the env so every consumer (store bootstrap, ParallelEnv) sees one
    # consistent contract, whichever launcher set it (ours: PADDLE_TRAINERS_NUM/
    # PADDLE_TRAINER_ID; external SLURM/mpirun-style: MASTER_ADDR+PADDLE_NNODES
    # with PADDLE_TRAINER_ID or RANK holding the process rank)
    os.environ["PADDLE_TRAINERS_NUM"] = str(world)
    os.environ.setdefault(
        "PADDLE_TRAINER_ID", os.environ.get("RANK", "0"))
    # load store.py by path: importing paddle_tpu.distributed (the package) pulls
    # in modules that may touch the backend, which must not happen yet
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "paddle_tpu._bootstrap_store",
        os.path.join(os.path.dirname(__file__), "distributed", "store.py"))
    store_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(store_mod)

    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    store = store_mod.create_or_get_global_tcp_store()
    _STORE[0] = store
    if rank == 0:
        coord = os.environ.get("PADDLE_JAX_COORDINATOR")
        if not coord:
            import socket

            s = socket.socket()
            s.bind(("", 0))
            free_port = s.getsockname()[1]
            s.close()
            host = store.host if store.host not in ("", "0.0.0.0") else "127.0.0.1"
            coord = f"{host}:{free_port}"
        store.set("jax/coordinator", coord)
    coord = store.get("jax/coordinator").decode()
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=world,
        process_id=rank,
        cluster_detection_method="deactivate",
    )
    store.barrier("early_init_distributed")
    _DONE[0] = True


def is_bootstrapped():
    return _DONE[0]


def _world_size_from_env():
    """Launcher contract (PADDLE_TRAINERS_NUM) with fallback to the external
    SLURM/mpirun-style contract (MASTER_ADDR + PADDLE_NNODES, one proc/node,
    rank in PADDLE_TRAINER_ID or RANK)."""
    if "PADDLE_TRAINERS_NUM" in os.environ:
        return int(os.environ["PADDLE_TRAINERS_NUM"])
    if os.environ.get("PADDLE_MASTER") or os.environ.get("MASTER_ADDR"):
        nnodes = int(os.environ.get("PADDLE_NNODES", "1"))
        if nnodes > 1 and ("PADDLE_TRAINER_ID" not in os.environ
                           and "RANK" not in os.environ):
            raise RuntimeError(
                "multi-node env detected (MASTER_ADDR + PADDLE_NNODES>1) but no "
                "rank variable: set PADDLE_TRAINER_ID or RANK per process")
        return nnodes
    return 1
