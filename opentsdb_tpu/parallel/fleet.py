"""Served mesh-plane membership: ``tsd --mesh-plane`` bootstrap.

``scripts/multihost_run.py --plane`` proved the mesh execution plane
across a real process boundary as a SMOKE; this module promotes it to a
deployment mode. Every ``tsd`` process launched with ``--mesh-plane
HOST:PORT`` joins one jax.distributed job (gloo TCP collectives on CPU,
the native transport on TPU pods) before the storage engine touches a
backend, so the fleet shares one device namespace and each process owns
its local slice of it.

Serving stays multi-controller: per-request collectives across
processes are impossible under jax's controller-per-host model (a
collective needs every process to enter the same program), so query
traffic never blocks on a peer. Instead each process shards its
RESIDENT HOT SET (storage/devshard.ShardedDeviceWindow) over its local
devices, and the fleet-level fan-out happens at the router, which
weights series ownership by each backend's advertised mesh width
(serve/router.py). The plane join buys the fleet:

- one coordinated device namespace (process_index/device ids are
  globally consistent — the reshard journal keys on them);
- boot-time membership checks (a misconfigured process fails loudly at
  join instead of silently serving an undersized hot set);
- the collective transport for offline legs (rollup rebuild fan-out)
  that DO run one program fleet-wide.

``init_plane`` is idempotent per process and must run BEFORE the first
jax backend touch — the CPU collectives implementation is latched at
backend init.
"""

from __future__ import annotations

import logging

LOG = logging.getLogger("opentsdb.fleet")

# The one plane this process joined (None until init_plane succeeds).
_PLANE: dict | None = None


def init_plane(coordinator: str, num_processes: int,
               process_id: int) -> dict:
    """Join the serving mesh plane. Returns the plane-info dict (also
    cached for ``plane_info()``): process id/count and the local/global
    device split the sharded hot set and the router weights build on.

    Raises on a malformed spec or a failed join — a daemon that was
    ASKED to be part of a mesh must not boot as a silent singleton.
    """
    global _PLANE
    if _PLANE is not None:
        return _PLANE
    if not coordinator or ":" not in coordinator:
        raise ValueError(
            f"--mesh-plane needs HOST:PORT, got {coordinator!r}")
    if num_processes < 1 or not 0 <= process_id < num_processes:
        raise ValueError(
            f"mesh plane process {process_id}/{num_processes} out of "
            f"range")
    import jax

    if num_processes > 1:
        # CPU fleets ride the gloo TCP transport (jax's default
        # jax_cpu_collectives_implementation); TPU pods join over
        # their native transport.
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)
    _PLANE = {
        "coordinator": coordinator,
        "process_id": int(jax.process_index()),
        "process_count": int(jax.process_count()),
        "devices_local": int(jax.local_device_count()),
        "devices_global": int(jax.device_count()),
    }
    LOG.info("joined mesh plane %s as process %d/%d (%d local / %d "
             "global devices)", coordinator, _PLANE["process_id"],
             _PLANE["process_count"], _PLANE["devices_local"],
             _PLANE["devices_global"])
    return _PLANE


def plane_info() -> dict | None:
    """The plane this process joined, or None outside mesh-plane
    mode. Read by /healthz, /stats and the /queries mesh section."""
    return _PLANE


def _reset_for_tests() -> None:
    global _PLANE
    _PLANE = None
