"""Multi-host runtime initialization — the DCN half of the network stack.

The reference's machine-list bootstrap (src/network/linkers_socket.cpp
Construct + config.h:261-268 machines/machine_list_file/num_machines/
local_listen_port) establishes a TCP ring/bruck topology.  On TPU the
whole layer collapses into the JAX distributed runtime: one
``jax.distributed.initialize`` call per process and every collective in
ops/grow.py rides ICI/DCN through XLA, with ``jax.devices()`` becoming
the GLOBAL device list so ``make_mesh`` spans processes automatically.

Process bootstrap accepts, in priority order:
1. env vars (the JAX-native deployment path):
   LIGHTGBM_TPU_COORDINATOR=host:port, LIGHTGBM_TPU_NUM_PROCESSES,
   LIGHTGBM_TPU_PROCESS_ID
2. the reference's config surface: ``machine_list_file`` / ``machines``
   ("host:port,host:port,...") + ``num_machines``; the FIRST machine is
   the coordinator (rank 0), and this process's rank is its line index
   (which must be given by LIGHTGBM_TPU_PROCESS_ID or inferred from the
   local hostname matching a list entry — the reference does the same
   hostname match in linkers_socket.cpp:90-134).

Row data in distributed mode: each process holds ITS OWN row shard (the
reference's pre_partition=true contract, config.h:116) and
``global_rows_array`` assembles the global jax.Array across processes.
"""

from __future__ import annotations

import os
import socket
from typing import Optional

import jax
import numpy as np

from ..utils.log import Log
from . import net

_initialized = False


def _bounded_initialize(coord: str, nproc: int, pid: int) -> None:
    """``jax.distributed.initialize`` under a watchdog with bounded
    retry.  The RPC layer's own
    ``initialization_timeout`` bounds a *reachable-but-refusing*
    coordinator; the watchdog additionally bounds a blackholed
    connection that never errors.  Returned errors retry on the net
    backoff schedule; a watchdog trip raises immediately (a second
    concurrent initialize on the same runtime is not safe)."""
    s = net.settings()
    deadline = s.deadline_s

    def _attempt():
        jax.distributed.initialize(
            coordinator_address=coord, num_processes=nproc, process_id=pid,
            initialization_timeout=max(int(round(deadline)), 1),
        )

    import time as _time

    delays = net.backoff_schedule(s.retries, s.backoff_base_s, s.backoff_max_s)
    t0 = _time.monotonic()
    for attempt in range(s.retries + 1):
        try:
            # the watchdog only trips when initialize neither returns
            # nor errors (a blackholed link); its trip is NOT retried
            # — a second concurrent initialize on the same runtime is
            # not safe while the first may still be in flight
            net.watchdog_call(_attempt, what="distributed.initialize",
                              deadline_s=deadline)
            return
        except net.NetError:
            raise
        except RuntimeError as e:
            msg = str(e)
            if "already" in msg or "only be called once" in msg:
                raise  # caller's already-initialized handling
            if attempt >= s.retries:
                elapsed = _time.monotonic() - t0
                raise net.CollectiveTimeoutError(
                    f"distributed bootstrap to {coord} failed after "
                    f"{attempt + 1} attempt(s) in {elapsed:.1f}s: {e}",
                    elapsed_s=elapsed,
                ) from e
            Log.warning(
                "distributed.initialize failed (attempt %d/%d): %s — "
                "retrying in %.2fs", attempt + 1, s.retries + 1, e,
                delays[attempt],
            )
            _time.sleep(delays[attempt])


def _machines_from_config(config) -> list:
    if getattr(config, "machine_list_file", ""):
        with open(config.machine_list_file) as f:
            return [ln.strip() for ln in f if ln.strip()]
    machines = getattr(config, "machines", "") or ""
    if machines:
        return [m.strip() for m in machines.split(",") if m.strip()]
    return []


def ensure_initialized(config=None, process_id: Optional[int] = None) -> bool:
    """Idempotently initialize the JAX distributed runtime when the run
    is multi-process.  Returns True when a multi-process runtime is (or
    already was) active."""
    global _initialized
    if config is not None:
        net.configure_from_config(config)
    if _initialized:
        return jax.process_count() > 1
    # NOTE: no jax.devices()/process_count() before initialize — any
    # backend query would lock in a single-process runtime.  Detect an
    # externally-initialized runtime via the distributed global state
    # (reading it does NOT initialize a backend).
    if net._client() is not None:
        _initialized = True
        if jax.process_count() > 1:
            net.ensure_heartbeat()
            from ..obs import tracer

            tracer.set_identity(rank=jax.process_index(),
                                world_size=jax.process_count())
            return True
        return False

    coord = os.environ.get("LIGHTGBM_TPU_COORDINATOR", "")
    nproc = int(os.environ.get("LIGHTGBM_TPU_NUM_PROCESSES", "0") or 0)
    pid_env = os.environ.get("LIGHTGBM_TPU_PROCESS_ID", "")
    pid = process_id if process_id is not None else (int(pid_env) if pid_env else None)

    if not coord and config is not None and getattr(config, "num_machines", 1) > 1:
        machines = _machines_from_config(config)
        if machines:
            coord = machines[0]
            nproc = nproc or int(config.num_machines)
            if pid is None:
                # hostname match, like linkers_socket.cpp:90-134; when
                # several list entries share this host, local_listen_port
                # disambiguates (multiple ranks per machine)
                local = {socket.gethostname(), socket.getfqdn(), "127.0.0.1", "localhost"}
                try:
                    local.add(socket.gethostbyname(socket.gethostname()))
                except OSError:
                    pass
                lport = str(getattr(config, "local_listen_port", ""))
                matches = [i for i, m in enumerate(machines) if m.split(":")[0] in local]
                if len(matches) > 1:
                    by_port = [
                        i for i in matches
                        if len(machines[i].split(":")) > 1
                        and machines[i].split(":")[1] == lport
                    ]
                    if len(by_port) == 1:
                        matches = by_port
                    else:
                        Log.fatal(
                            "Cannot infer this process's rank: %d machine-list "
                            "entries match the local host and local_listen_port "
                            "does not disambiguate; set LIGHTGBM_TPU_PROCESS_ID",
                            len(matches),
                        )
                if matches:
                    pid = matches[0]
    if not coord or not nproc or pid is None:
        return False

    Log.info(
        "Initializing distributed runtime: coordinator=%s rank=%d/%d "
        "(deadline=%.0fs, retries=%d)",
        coord, pid, nproc, net.settings().deadline_s, net.settings().retries,
    )
    try:
        _bounded_initialize(coord, pid=pid, nproc=nproc)
    except net.NetError:
        # an explicitly-requested multi-process bootstrap that cannot be
        # established fails LOUDLY and bounded (linkers_socket.cpp does
        # the same after its connect retries) — silently continuing
        # single-process would train on a fraction of the data
        raise
    except RuntimeError as e:  # backend already up (too late) or re-init
        msg = str(e)
        if "already" in msg or "only be called once" in msg:
            _initialized = True
            if jax.process_count() > 1:
                net.ensure_heartbeat()
                return True
            return False
        Log.warning("Distributed init failed: %s", e)
        return False
    _initialized = True
    # backend-init probe: the first backend query after initialize can
    # itself hang on an unreachable backend — bound it like any other
    # collective
    nproc_seen = net.watchdog_call(jax.process_count,
                                   what="backend_init_probe")
    if nproc_seen > 1:
        net.ensure_heartbeat()
        # stamp rank/world/run_id onto every trace record so `report
        # merge` can correlate the per-rank JSONLs of this run
        from ..obs import tracer

        tracer.set_identity(rank=jax.process_index(),
                            world_size=nproc_seen, run_id=coord)
    return nproc_seen > 1


def is_multiprocess() -> bool:
    return jax.process_count() > 1


def global_rows_array(local_rows, mesh, row_axis: str = "data"):
    """Assemble a row-sharded global jax.Array from this process's local
    row block (the pre-partitioned data contract).  Single-process meshes
    pass through unchanged."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    if jax.process_count() == 1:
        return jnp.asarray(local_rows)
    spec = P(row_axis, *([None] * (np.ndim(local_rows) - 1)))
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_process_local_data(sharding, np.asarray(local_rows))


def replicated_array(value, mesh):
    """Replicate identical per-process data onto a multi-process mesh."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    if jax.process_count() == 1:
        return jnp.asarray(value)
    sharding = NamedSharding(mesh, P())
    return jax.make_array_from_process_local_data(sharding, np.asarray(value))


def current_epoch() -> int:
    """The live membership epoch of this process's fleet — the
    generation stamp elastic transitions bump (parallel/membership.py).
    Static jax.distributed worlds and unarmed runs report 0, so any
    caller can stamp epoch-sensitive state (collect.py uid scoping,
    checkpoint meta, observability rows) without caring whether the
    world is elastic."""
    from . import membership

    rt = membership.runtime()
    return max(rt.epoch, 0) if rt is not None else 0
