"""Distributed environment basics (rank/world-size/init).

Reference: python/paddle/distributed/parallel.py (ParallelEnv, PADDLE_* env
vars). TPU-native: jax.distributed coordination service replaces TCPStore;
env vars keep the same names so launch-CLI parity holds.
"""
from __future__ import annotations

import os

import jax

_initialized = False


def get_rank() -> int:
    if jax.process_count() > 1:
        return jax.process_index()
    return int(os.environ.get("PADDLE_TRAINER_ID", "0"))


def get_world_size() -> int:
    if jax.process_count() > 1:
        return jax.process_count()
    return int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))


def is_initialized() -> bool:
    return _initialized


def init_parallel_env():
    """Reference: parallel.py init_parallel_env — rendezvous + process group
    bring-up. Here: jax.distributed.initialize when multi-host env vars are
    present (coordination service over DCN); single-host is a no-op.

    NOTE: must run before anything touches the XLA backend — so the env-var
    check comes first and no jax query (process_count/devices) happens
    before initialize."""
    global _initialized
    if _initialized:
        return
    coord = os.environ.get("PADDLE_MASTER") or \
        os.environ.get("MASTER_ADDR")
    nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    pid = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    if coord and nprocs > 1:
        port = os.environ.get("MASTER_PORT", coord.split(":")[-1]
                              if ":" in coord else "8476")
        if not jax.distributed.is_initialized():
            jax.distributed.initialize(
                coordinator_address=f"{coord.split(':')[0]}:{port}",
                num_processes=nprocs, process_id=pid)
    # elastic liveness: auto-beat when the launcher asked for it
    try:
        from . import heartbeat as _hb
        _hb.start()
        # multi-host relay: rank 0 mirrors every rank's KV beats into
        # the primary controller's heartbeat dir so its file watcher
        # covers hosts with no shared filesystem
        relay_dir = os.environ.get("PADDLE_HEARTBEAT_KV_RELAY")
        if relay_dir and get_rank() == 0:
            _hb.start_kv_relay(relay_dir, range(get_world_size()))
    except Exception:
        pass
    _initialized = True


class ParallelEnv:
    """Reference: parallel.py ParallelEnv."""

    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def local_rank(self):
        return int(os.environ.get("PADDLE_RANK_IN_NODE", "0"))

    @property
    def nranks(self):
        return get_world_size()

    @property
    def dev_id(self):
        return self.local_rank
