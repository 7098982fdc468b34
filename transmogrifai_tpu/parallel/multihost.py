"""Multi-host initialization — the DCN story (SURVEY §2.6 P7).

The reference's cross-executor traffic rides Spark's netty shuffle; here
cross-HOST traffic is jax's distributed runtime: every host calls
``init_distributed()`` (coordinator address + process id, or nothing under a
supported cluster environment), after which ``jax.devices()`` spans all hosts
and the SAME mesh/sharding code in this package rides ICI within a slice and
DCN across slices — no separate transport layer exists or is needed.

Typical launch (one line per host)::

    from transmogrifai_tpu.parallel import init_distributed, make_mesh
    init_distributed()          # auto-detected under TPU pods / GKE
    mesh = make_mesh()          # all hosts' devices, rows over 'data'

Single-process runs are a no-op, so library code can call this
unconditionally.
"""

from __future__ import annotations

from typing import Optional

import jax

from ..resilience import maybe_inject, record_failure, run_with_deadline


#: Env vars that name a coordinator / TPU-pod topology outright: their
#: presence alone is enough to attempt auto-init.
_COORDINATOR_ENV_VARS = (
    "COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS",
    "MEGASCALE_COORDINATOR_ADDRESS", "TPU_WORKER_HOSTNAMES",
    "CLOUD_TPU_TASK_ID",
)

#: Env vars that carry the scheduler's world size.  A bare job id
#: (SLURM_JOB_ID) is NOT here on purpose: a single-node SLURM job used to
#: trip auto-init on it and "degrade" to single-host every run — only a
#: world size > 1 means there are actually peers to rendezvous with.
_WORLD_SIZE_ENV_VARS = (
    "SLURM_NTASKS", "SLURM_NPROCS", "OMPI_COMM_WORLD_SIZE", "PMI_SIZE",
)

# kept for back-compat introspection (tests/dashboards list it)
_CLUSTER_ENV_VARS = _COORDINATOR_ENV_VARS + _WORLD_SIZE_ENV_VARS


def _world_size_env() -> int:
    """Largest world size any scheduler env var claims (0 when none do)."""
    import os
    n = 0
    for v in _WORLD_SIZE_ENV_VARS:
        raw = os.environ.get(v)
        if not raw:
            continue
        try:
            n = max(n, int(raw))
        except ValueError:
            continue
    return n


def _cluster_env_present() -> bool:
    """Only auto-detect when the environment names a coordinator or claims
    a world size > 1 — a lone SLURM_JOB_ID (single-node job) must not
    trigger an observably-failing distributed init attempt."""
    import os
    if any(os.environ.get(v) for v in _COORDINATOR_ENV_VARS):
        return True
    return _world_size_env() > 1


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     timeout_s: Optional[float] = None) -> bool:
    """Initialize jax's distributed runtime (idempotent, single-process safe).

    Returns True when a multi-process runtime is active after the call.
    Auto-detection only runs under a recognizable cluster environment (TPU
    pod / GKE / SLURM / MPI env vars) — probing jax's auto-detect on plain
    single-host machines can hard-abort the process, so without a coordinator
    and without cluster env vars this is a clean no-op.

    ``timeout_s`` runs the init under a watchdog: it can HANG in native
    code with no error raised, and a
    hang must surface as ``WatchdogTimeout`` — raised for an explicit
    coordinator request, recorded in the failure log and degraded to
    single-host for auto-detection.

    .. note:: the watchdog can only *abandon* a hung native init thread, it
       cannot reclaim it (the thread leaks; ``watchdog.abandoned_total``
       counts them).  Callers that need the hang actually killed must
       pre-flight with the subprocess-isolated
       ``parallel.supervisor.probe_devices`` — a child process under
       SIGTERM→SIGKILL escalation is the only reclaim that works.

    Emits a ``multihost.init`` telemetry span around the attempt and sets
    the ``multihost.process_count`` / ``multihost.initialized`` gauges, so
    a degraded-to-single-host run is visible on dashboards and not just in
    the failure log.
    """
    from ..telemetry import REGISTRY, span
    if jax.distributed.is_initialized():
        REGISTRY.gauge("multihost.initialized").set(1)
        REGISTRY.gauge("multihost.process_count").set(jax.process_count())
        return jax.process_count() > 1
    if coordinator_address is None and not _cluster_env_present():
        return False
    try:
        with span("multihost.init",
                  coordinator=coordinator_address or "auto",
                  requested_processes=int(num_processes or 0),
                  timeout_s=float(timeout_s or 0)):
            maybe_inject("multihost.init", key=coordinator_address or "auto")
            run_with_deadline(
                jax.distributed.initialize, timeout_s,
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id,
                description="jax.distributed.initialize")
    except Exception as e:  # noqa: BLE001
        REGISTRY.gauge("multihost.initialized").set(0)
        # known truth on EVERY exit path: init failed, this process is
        # single — a stale >1 from a prior run must not survive the raise
        REGISTRY.gauge("multihost.process_count").set(1)
        if coordinator_address is not None:
            # an EXPLICIT multi-host request that fails must not silently
            # degrade to single-host (every host would train divergently)
            raise
        # auto-detected cluster env but init failed: degrade to single-host,
        # observably — exactly the demotion the round-5 probes did by hand
        record_failure("multihost.init_distributed", "degraded", e,
                       point="multihost.init", fallback="single-host")
        return False
    REGISTRY.gauge("multihost.initialized").set(1)
    REGISTRY.gauge("multihost.process_count").set(jax.process_count())
    return jax.process_count() > 1


def ensure_cpu_collectives(implementation: str = "gloo") -> bool:
    """Select a cross-process collectives backend for the CPU client.

    jax's default CPU client has none: a multi-process CPU group can
    ``init_distributed`` fine and then fail every computation over a
    cross-process array with "Multiprocess computations aren't implemented
    on the CPU backend".  Selecting gloo *before the backend first
    initializes* makes the 2-process CI host group run real cross-process
    collectives.  Best-effort: harmless (and a recorded no-op) on builds
    without the option or after the backend is already live."""
    from ..telemetry import REGISTRY
    try:
        jax.config.update("jax_cpu_collectives_implementation",
                          implementation)
    except Exception as e:  # noqa: BLE001 — option absent / backend live
        record_failure("multihost.cpu_collectives", "swallowed", e,
                       point="multihost.cpu_collectives",
                       implementation=implementation)
        REGISTRY.gauge("multihost.cpu_collectives").set(0)
        return False
    REGISTRY.gauge("multihost.cpu_collectives").set(1)
    return True


def is_multihost() -> bool:
    return jax.process_count() > 1
