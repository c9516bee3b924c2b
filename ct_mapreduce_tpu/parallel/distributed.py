"""Multi-host initialization and TPU-native coordination.

The reference's scale-out control plane is Redis SETNX leader election
plus a polled start barrier (/root/reference/coordinator/
coordinator.go:44-138). The TPU-native equivalent (SURVEY.md §2.3 role
2):

- ``initialize_multihost`` wraps ``jax.distributed.initialize`` — the
  JAX runtime's coordination service IS the election (process 0 hosts
  the coordinator, everyone else connects to it over DCN);
- leadership is ``process_index == 0`` — deterministic, no contention,
  renewed implicitly by the runtime's health checks rather than a
  lease-renewal thread;
- the start barrier is a collective: an all-reduce over every
  addressable device rides ICI/DCN and unblocks all hosts at once,
  instead of followers polling Redis every 250 ms.

:class:`DistributedCoordinator` exposes the reference Coordinator's
interface (await_leader / await_start / send_start) on top of these so
callers can swap fabrics by construction alone.
"""

from __future__ import annotations

from typing import Optional


# Env vars whose presence signals a multi-host environment where
# argument-less jax.distributed.initialize() can autodetect peers.
_AUTODETECT_ENV = (
    "JAX_COORDINATOR_ADDRESS",
    "COORDINATOR_ADDRESS",
    "MEGASCALE_COORDINATOR_ADDRESS",
    "TPU_WORKER_HOSTNAMES",
)


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Bring up jax.distributed.

    Must run before any JAX computation (jax.distributed's own
    contract) — so this deliberately avoids jax.process_count() or any
    other backend-initializing call before initialize(). With explicit
    arguments it initializes directly; with none, it autodetects iff a
    multi-host environment variable is present, else stays local.
    No-ops when the distributed client already exists."""
    import os

    import jax

    if _already_initialized():
        return
    if coordinator_address is None and num_processes is None:
        if not any(os.environ.get(k) for k in _AUTODETECT_ENV):
            return  # single-host: no coordination service needed
        jax.distributed.initialize()
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def _already_initialized() -> bool:
    from jax._src import distributed

    return distributed.global_state.client is not None


def kv_put(key: str, value: str) -> bool:
    """Publish a value on the jax.distributed coordination service's
    key-value store (the fleet coordinator's epoch/shutdown fabric on
    TPU pods). Returns False when no distributed client exists (single
    process) or the runtime lacks the KV API — callers degrade to
    local state. Overwrite is emulated by delete-then-set where the
    runtime forbids re-setting a key."""
    from jax._src import distributed

    client = distributed.global_state.client
    if client is None:
        return False
    try:
        delete = getattr(client, "key_value_delete", None)
        if delete is not None:
            try:
                delete(key)
            except Exception:
                pass  # absent key / runtime without delete semantics
        client.key_value_set(key, value)
        return True
    except Exception:
        return False


def kv_get(key: str) -> Optional[str]:
    """Non-blocking read of a coordination-service KV entry; None when
    absent, unreadable, or there is no distributed client."""
    from jax._src import distributed

    client = distributed.global_state.client
    if client is None:
        return None
    try_get = getattr(client, "key_value_try_get", None)
    if try_get is None:
        return None
    try:
        return try_get(key)
    except Exception:
        return None  # NotFound surfaces as an exception


def is_leader() -> bool:
    """Host-0 leadership — the fixed, contention-free analog of winning
    the SETNX election."""
    import jax

    return jax.process_index() == 0


def device_barrier(tag: str = "barrier") -> None:
    """Block until every process reaches the barrier: a 1-element
    psum over all devices forces a synchronizing collective."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = np.asarray(jax.devices())
    mesh = Mesh(devices, ("all",))

    @jax.jit
    def _reduce(x):
        return shard_map(
            lambda v: jax.lax.psum(v, "all"),
            mesh=mesh,
            in_specs=P("all"),
            out_specs=P(),
        )(x)

    x = jax.device_put(
        jnp.ones((devices.size,), jnp.int32), NamedSharding(mesh, P("all"))
    )
    total = int(_reduce(x)[0])  # local slice is [1]; psum → replicated [1]
    if total != devices.size:
        raise RuntimeError(f"barrier psum returned {total} != {devices.size}")


class DistributedCoordinator:
    """Reference-Coordinator interface over jax.distributed.

    await_leader: returns host-0 status (no contention to win).
    send_start / await_start: both sides enter the device barrier — the
    leader's entry releases the followers, like publishing
    ``started-<id>`` does in the Redis protocol.
    """

    def __init__(self, name: str = "ct-fetch"):
        self.name = name
        self.is_leader = False
        self.identifier = ""

    def await_leader(self) -> bool:
        import jax

        self.is_leader = is_leader()
        self.identifier = f"jax-process-{jax.process_index()}"
        return self.is_leader

    def await_start(self, timeout_s: Optional[float] = None) -> None:
        if not self.identifier:
            raise RuntimeError("Must not call before await_leader completes")
        if self.is_leader:
            raise RuntimeError("Must not call unless we're a follower")
        if timeout_s is None:
            device_barrier(f"start-{self.name}")
            return
        # Collectives have no native timeout; honor the contract by
        # waiting on a worker thread. On expiry the thread (and its
        # pending collective) is abandoned — the caller is expected to
        # treat TimeoutError as fatal for this process, like the
        # reference's polled barrier timeout.
        import threading

        err: list[BaseException] = []

        def run():
            try:
                device_barrier(f"start-{self.name}")
            except BaseException as e:  # surfaced to the caller below
                err.append(e)

        t = threading.Thread(target=run, daemon=True)
        t.start()
        t.join(timeout_s)
        if t.is_alive():
            raise TimeoutError("start barrier")
        if err:
            raise err[0]

    def send_start(self) -> None:
        if not self.identifier:
            raise RuntimeError("Must not call before await_leader completes")
        if not self.is_leader:
            raise RuntimeError("Must not call unless we're leader")
        device_barrier(f"start-{self.name}")

    def close(self) -> None:
        pass
