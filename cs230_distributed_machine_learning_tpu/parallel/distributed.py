"""Multi-process SPMD runtime: one logical mesh spanning TPU-VM hosts.

The reference scales by adding worker containers, each a private process
(``aws-prod/docker-compose.yml:133-199``); a TPU pod *slice* (v5e-16+) is
different — its chips are spread over hosts that must act as ONE program
(multi-controller SPMD). This module carries the three pieces the agent
needs for that:

- :func:`init_distributed` — join the JAX distributed runtime
  (``jax.distributed.initialize``); after it, ``jax.devices()`` is the
  global device list and a Mesh built over it spans hosts, with XLA
  inserting cross-host collectives (ICI within a slice, gloo on CPU test
  fleets).
- :func:`broadcast_json` — control-plane fan-out: process 0 (the only one
  talking REST to the coordinator) replicates each task batch to every
  process, so all of them enter the same sharded computation in lockstep.
  Size-bucketed so recurring batch shapes reuse one compiled broadcast.
- :func:`fetch` — the host-side read of a trial-sharded result: assembles
  the global value on every process (``process_allgather``) since only
  process 0 reports it upstream.

Tested by ``tests/test_distributed_mesh.py`` (two CPU processes x 4
virtual devices forming one 8-device mesh through the full REST job path).
"""

from __future__ import annotations

import json
from typing import Any, Optional

import numpy as np


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    *,
    local_device_count: Optional[int] = None,
) -> None:
    """Join the multi-process JAX runtime (idempotent per process).

    On TPU VMs all arguments may be ``None`` — ``jax.distributed`` infers
    the topology from the TPU metadata. On CPU (tests/dev fleets) pass all
    three and optionally ``local_device_count`` to fan one process into N
    virtual devices; the CPU cross-process collective backend (gloo) is
    enabled automatically.
    """
    import os
    import re

    if local_device_count:
        flags = os.environ.get("XLA_FLAGS", "")
        flag = f"--xla_force_host_platform_device_count={local_device_count}"
        if "xla_force_host_platform_device_count" in flags:
            # an inherited flag (test harnesses export =8) must not silently
            # win over the explicit request — mismatched per-rank device
            # counts would corrupt the global mesh topology
            new_flags = re.sub(
                r"--?xla_force_host_platform_device_count=\d+", flag, flags
            )
            if new_flags != flags:
                from ..utils.logging import get_logger

                get_logger().warning(
                    "overriding inherited xla_force_host_platform_device_count"
                    " with --local-devices=%d", local_device_count,
                )
            os.environ["XLA_FLAGS"] = new_flags
        else:
            os.environ["XLA_FLAGS"] = f"{flags} {flag}".strip()

    import jax

    from ..utils.jax_setup import setup_jax

    setup_jax()
    if os.environ.get("TPUML_PLATFORM") == "cpu" or local_device_count:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)


def process_index() -> int:
    import jax

    return jax.process_index()


def is_primary() -> bool:
    """True on the (single) process that owns the DCN control plane."""
    return process_index() == 0


def is_multiprocess() -> bool:
    import jax

    return jax.process_count() > 1


def fetch(tree: Any) -> Any:
    """Device->host: numpy leaves for a (possibly cross-process) pytree.

    Single-process arrays convert directly; fully-replicated global arrays
    read their local copy; trial-sharded global arrays are assembled with a
    ``process_allgather`` (a collective — every process must call fetch on
    the same values in the same order, which the lockstep agent loop
    guarantees).
    """
    import jax

    def one(a):
        if not isinstance(a, jax.Array):
            return np.asarray(a)
        if a.is_fully_addressable or a.is_fully_replicated:
            return np.asarray(a)
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(a, tiled=True))

    prefetch_async(tree)
    return jax.tree_util.tree_map(one, tree)


def prefetch_async(tree: Any) -> None:
    """Start device->host copies for every addressable array leaf NOW.

    Every blocking host conversion (``np.asarray``) is its own
    device->host round trip, and converting leaf-by-leaf pays them
    SERIALLY — the cost floor of tiny jobs. Issuing
    ``copy_to_host_async`` on every leaf first lets the copies ride the
    link concurrently; the conversions that follow find their bytes
    already on host. Non-addressable (cross-process) leaves are left for
    the collective path in ``fetch``.
    """
    import jax

    def start(a):
        if isinstance(a, jax.Array) and (
            a.is_fully_addressable or a.is_fully_replicated
        ):
            try:
                a.copy_to_host_async()
            except Exception:  # best-effort: conversion still works
                pass

    jax.tree_util.tree_map(start, tree)


#: floor for the broadcast payload bucket: recurring small task batches all
#: land in one bucket -> one compiled broadcast executable
_MIN_BUCKET = 4096


def broadcast_json(obj: Any = None) -> Any:
    """Replicate ``obj`` (JSON-serializable) from process 0 to all.

    Every process must call this at the same point (collective). Non-zero
    processes ignore their ``obj``. Payloads are padded to power-of-two
    buckets so the underlying broadcast compiles once per bucket, not once
    per message length.
    """
    from jax.experimental import multihost_utils

    if is_primary():
        payload = np.frombuffer(
            json.dumps(obj).encode("utf-8"), dtype=np.uint8
        ).copy()
        n = payload.size
    else:
        payload = np.zeros((0,), np.uint8)
        n = 0
    n = int(multihost_utils.broadcast_one_to_all(np.int32(n)))
    bucket = max(_MIN_BUCKET, 1 << max(int(n) - 1, 0).bit_length())
    buf = np.zeros((bucket,), np.uint8)
    buf[: payload.size] = payload
    buf = np.asarray(multihost_utils.broadcast_one_to_all(buf))
    return json.loads(bytes(buf[:n]).decode("utf-8"))
