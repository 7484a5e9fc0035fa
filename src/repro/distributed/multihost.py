"""True multi-process DCF-PCA execution (DESIGN.md Sec. 14).

The paper's scaling claim is that one consensus round ships only the
small (m, r) factor per client.  Everything in ``dcf_pca_sharded`` is
plain SPMD (``shard_map`` + ``psum``/``pmean``/``all_gather`` over named
mesh axes), so the *same jitted program* runs over a mesh whose devices
span OS processes -- the collectives then cross a real process boundary
instead of a single runtime's address space.  This module provides the
three pieces that turn that from a statement into an executable setup:

* **bootstrap** -- ``jax.distributed.initialize`` with the gloo CPU
  collectives backend selected *before* backend init (the default CPU
  backend rejects multi-process computations), plus an env-var protocol
  (``RPCA_COORDINATOR`` / ``RPCA_NUM_PROCESSES`` / ``RPCA_PROCESS_ID``)
  so worker code only calls :func:`initialize_from_env`.
* **CPU CI harness** -- :func:`launch_workers` spawns N Python worker
  processes on one box, each pinned to the CPU platform with
  ``--xla_force_host_platform_device_count`` so a laptop/CI runner
  exercises the genuine multi-process collective path.
* **wire accounting** -- the modelled bytes a consensus round moves per
  client (dense all-reduce vs top-k compressed all-gather; see
  :func:`consensus_wire_model`) and process-wide traffic counters the
  solver registry adapters feed and ``RPCAService.metrics()`` reports.

Import stays light: nothing here touches JAX until a bootstrap/mesh
function is called, so ``repro.core.dcf_pca`` can import the traffic
recorder without dragging device init forward.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
import threading
import time

from repro.core import factorized as fz

ENV_COORDINATOR = "RPCA_COORDINATOR"
ENV_NUM_PROCESSES = "RPCA_NUM_PROCESSES"
ENV_PROCESS_ID = "RPCA_PROCESS_ID"
ENV_LOCAL_DEVICES = "RPCA_LOCAL_DEVICES"

_FORCE_FLAG = "--xla_force_host_platform_device_count"


# ---------------------------------------------------------------------------
# bootstrap


def _force_host_devices(n: int) -> None:
    """Request ``n`` CPU devices for this process (before backend init)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _FORCE_FLAG in flags:
        return
    os.environ["XLA_FLAGS"] = f"{flags} {_FORCE_FLAG}={n}".strip()


def bootstrap(coordinator: str, num_processes: int, process_id: int,
              local_devices: int = 1, *,
              connect_timeout_s: float = 120.0,
              connect_attempts: int = 4,
              backoff_s: float = 0.5) -> None:
    """Join the ``num_processes``-wide JAX distributed runtime.

    Must run before the first JAX computation in this process.  On CPU
    the default collectives implementation rejects cross-process
    programs ("Multiprocess computations aren't implemented on the CPU
    backend"), so the gloo implementation is selected first -- that
    config knob is read at backend initialization time.

    Connection setup is fault-tolerant: the coordinator dial gets a
    bounded ``connect_timeout_s`` (instead of the runtime default) and a
    failed attempt is retried up to ``connect_attempts`` times with
    exponential backoff (``backoff_s * 2**attempt`` sleeps) -- a worker
    that races a still-binding (or restarting) coordinator joins once it
    comes up rather than dying on the first refused connection.
    """
    if local_devices > 1:
        _force_host_devices(local_devices)
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    kwargs = dict(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    for attempt in range(max(1, connect_attempts)):
        try:
            # int(): the underlying pybind client rejects a float timeout
            # *after* the coordinator service exists.
            jax.distributed.initialize(
                **kwargs, initialization_timeout=int(connect_timeout_s))
            return
        except RuntimeError as e:
            if "only be called once" in str(e):
                raise  # a live runtime already exists: not retryable
            if attempt + 1 >= max(1, connect_attempts):
                raise
            try:
                jax.distributed.shutdown()  # clear the failed half-init
            except Exception:  # pragma: no cover - nothing to clear
                pass
            time.sleep(backoff_s * (2 ** attempt))


def initialize_from_env() -> bool:
    """Bootstrap from the ``RPCA_*`` worker env vars; no-op when absent.

    Returns True when this process joined a distributed runtime.  Worker
    scripts call this once at the top; the same script then runs both
    standalone (vars unset) and under :func:`launch_workers`.
    """
    coord = os.environ.get(ENV_COORDINATOR)
    if not coord:
        return False
    bootstrap(
        coord,
        int(os.environ[ENV_NUM_PROCESSES]),
        int(os.environ[ENV_PROCESS_ID]),
        local_devices=int(os.environ.get(ENV_LOCAL_DEVICES, "1")),
    )
    return True


def free_port() -> int:
    """An OS-assigned free TCP port for the coordinator."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# mesh helpers


def multihost_mesh(axes: tuple[str, ...] = ("data",),
                   shape: tuple[int, ...] | None = None):
    """A mesh over *all* processes' devices (global device order).

    Defaults to one ``data`` axis spanning every device in the
    distributed runtime; pass ``shape``/``axes`` for a data x model
    layout.  Requires :func:`bootstrap` (or a single-process runtime,
    where it degenerates to a local mesh).
    """
    import jax

    devices = jax.devices()
    if shape is None:
        shape = (len(devices),)
    import numpy as np

    return jax.sharding.Mesh(
        np.asarray(devices).reshape(shape), axes)


def is_multiprocess_mesh(mesh) -> bool:
    """True when the mesh's devices span more than one OS process."""
    if mesh is None:
        return False
    return len({d.process_index for d in mesh.devices.flat}) > 1


# ---------------------------------------------------------------------------
# CPU CI worker harness

_PREAMBLE = """\
import repro.distributed.multihost as _mh
_mh.initialize_from_env()
"""


#: stderr/stdout markers of a coordinator port-bind loss: ``free_port``
#: probes a port and closes it before worker 0 re-binds it, so another
#: process can win the race -- retried with a fresh port, not a flake.
_BIND_RACE_MARKERS = ("Address already in use", "Failed to bind",
                     "bind_address")


def _launch_once(code: str, num_processes: int, devices_per_process: int,
                 timeout: int, extra_env: dict[str, str] | None,
                 kill_after: dict[int, float] | None) -> list[str]:
    """One worker-cohort launch (see :func:`launch_workers`)."""
    src_dir = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", ".."))
    coord = f"127.0.0.1:{free_port()}"
    base_env = dict(os.environ)
    base_env.pop("XLA_FLAGS", None)
    base_env.update(extra_env or {})
    base_env["JAX_PLATFORMS"] = "cpu"
    base_env[ENV_COORDINATOR] = coord
    base_env[ENV_NUM_PROCESSES] = str(num_processes)
    base_env[ENV_LOCAL_DEVICES] = str(devices_per_process)
    base_env["XLA_FLAGS"] = f"{_FORCE_FLAG}={devices_per_process}"
    base_env["PYTHONPATH"] = src_dir + os.pathsep + base_env.get(
        "PYTHONPATH", "")

    procs = []
    timers: list[threading.Timer] = []
    for pid in range(num_processes):
        env = dict(base_env)
        env[ENV_PROCESS_ID] = str(pid)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _PREAMBLE + code],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        ))
    for pid, delay in (kill_after or {}).items():
        t = threading.Timer(float(delay), procs[int(pid)].kill)
        t.daemon = True
        t.start()
        timers.append(t)
    outs: list[str] = []
    fail: str | None = None
    try:
        for pid, p in enumerate(procs):
            try:
                out, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise
            outs.append(out)
            if p.returncode != 0 and fail is None:
                fail = f"worker {pid} exited {p.returncode}:\n{out}"
    finally:
        for t in timers:
            t.cancel()
    if fail is not None:
        raise RuntimeError(fail)
    return outs


def launch_workers(code: str, num_processes: int = 2,
                   devices_per_process: int = 1, timeout: int = 900,
                   extra_env: dict[str, str] | None = None, *,
                   kill_after: dict[int, float] | None = None,
                   max_restarts: int = 0,
                   bind_retries: int = 3) -> list[str]:
    """Run ``code`` in ``num_processes`` fresh Python worker processes.

    Each worker gets the ``RPCA_*`` coordination env, the CPU platform,
    ``devices_per_process`` forced host devices, and ``src`` on its
    ``PYTHONPATH``; ``initialize_from_env()`` has already run when
    ``code`` starts.  Returns each worker's stdout (index = process_id);
    raises ``RuntimeError`` with the offender's output on any nonzero
    exit.  This is the CI stand-in for a real multi-host launch -- the
    collective path exercised is identical, only the transport is local.

    Fault tolerance:

    * **Coordinator bind race.**  ``free_port()`` probes a port and
      closes it before worker 0 binds it, so another process can grab it
      in between.  A cohort that fails with a bind-error marker is
      relaunched on a fresh port (up to ``bind_retries`` times, with
      backoff) instead of surfacing the race as a flake.
    * **Deterministic crashes.**  ``kill_after={pid: seconds}`` SIGKILLs
      the given workers after a fixed delay on the *first* launch -- the
      chaos hook for crash/recovery tests.  With ``max_restarts > 0`` a
      failed cohort (killed or crashed) is respawned whole, fresh
      coordinator port, same ``code``, up to that many times; worker
      code that resumes from its latest checkpoint turns this into the
      kill -> respawn -> finish-bit-exact drill.  Kills fire only on the
      first launch so a restarted cohort runs to completion.
    """
    last: Exception | None = None
    for attempt in range(max_restarts + 1):
        binds = 0
        while True:
            try:
                return _launch_once(
                    code, num_processes, devices_per_process, timeout,
                    extra_env, kill_after if attempt == 0 else None,
                )
            except RuntimeError as e:
                if (any(m in str(e) for m in _BIND_RACE_MARKERS)
                        and binds < bind_retries):
                    binds += 1
                    time.sleep(0.2 * (2 ** (binds - 1)))
                    continue
                last = e
                break
        if attempt >= max_restarts:
            break
    assert last is not None
    raise last


# ---------------------------------------------------------------------------
# consensus wire accounting


def consensus_wire_model(m: int, rank: int, num_clients: int,
                         compress=None) -> dict[str, float]:
    """Modelled consensus bytes one client moves per round.

    Dense: ship the local (m, r) f32 factor up and receive the consensus
    factor down -- ``2 m r * 4`` bytes (the paper's ``2 E m r`` bound
    over ``E`` clients).  Compressed: the consensus runs as an
    all-gather of each client's top-k (value f32, index int32) payload,
    so a client sends ``k * 8`` and receives ``(E-1) * k * 8`` --
    ``E k * 8`` total.  Index bytes are counted: a top-k payload that
    "forgot" its int32 indices would overstate savings 2x.
    """
    d = m * rank
    dense = 2 * d * 4
    frac = getattr(compress, "topk_frac", None) if compress is not None \
        else None
    if frac is None:
        shipped = dense
        k = d
    else:
        k = fz.topk_k(d, float(frac))
        shipped = 8 * k * num_clients
    return {
        "dense_bytes": float(dense),
        "shipped_bytes": float(shipped),
        "ratio": dense / shipped,
        "k": float(k),
    }


_traffic_lock = threading.Lock()
_TRAFFIC = {
    "solves": 0,
    "rounds": 0,
    "shipped_bytes": 0.0,
    "dense_bytes": 0.0,
}
#: Recorded solves not yet folded into ``_TRAFFIC``: ``(rounds, budget,
#: shipped bytes a round, dense bytes a round)``, where ``rounds`` may be
#: the solve's own device array, still being computed.
_PENDING: list[tuple[object, int, float, float]] = []
#: Pending solves that trigger a fold at the next record (bounds the list
#: in a process that never reads the counters).
_PENDING_CAP = 1024


def record_consensus(m: int, rank: int, num_clients: int, rounds,
                     compress=None, budget: int = 0) -> None:
    """Fold one solve's modelled consensus traffic into the counters.

    ``rounds`` is the solve's executed round count: an int, or its
    ``SolveStats.rounds`` array (one entry a problem when batched).  The
    array is kept as it is and summed when the counters are read, so
    recording never waits for the device.  ``budget`` is counted instead
    where the array cannot be read back (another process's shards).
    """
    model = consensus_wire_model(m, rank, num_clients, compress)
    with _traffic_lock:
        if len(_PENDING) >= _PENDING_CAP:
            _fold()
        _TRAFFIC["solves"] += 1
        _PENDING.append((rounds, budget, model["shipped_bytes"],
                         model["dense_bytes"]))


def _fold() -> None:
    """Sum the pending solves' rounds into ``_TRAFFIC`` (lock held)."""
    import numpy as np

    for rounds, budget, shipped, dense in _PENDING:
        try:
            n = int(np.asarray(rounds).sum())
        except RuntimeError:  # not addressable from this process
            n = budget
        _TRAFFIC["rounds"] += n
        _TRAFFIC["shipped_bytes"] += shipped * n
        _TRAFFIC["dense_bytes"] += dense * n
    _PENDING.clear()


def consensus_traffic(reset: bool = False) -> dict[str, float]:
    """Snapshot of the process-wide consensus traffic counters.

    ``bytes_per_round`` is the modelled per-client shipped bytes
    averaged over recorded rounds; ``achieved_ratio`` the realized
    dense/shipped compression (1.0 when every solve ran dense).  Reading
    waits for the recorded solves' round counts.
    """
    with _traffic_lock:
        _fold()
        snap = dict(_TRAFFIC)
        if reset:
            for key in _TRAFFIC:
                _TRAFFIC[key] = type(_TRAFFIC[key])(0)
    rounds = max(snap["rounds"], 1)
    shipped = snap["shipped_bytes"]
    return {
        "solves": snap["solves"],
        "rounds": snap["rounds"],
        "shipped_bytes": shipped,
        "bytes_per_round": shipped / rounds,
        "achieved_ratio": (snap["dense_bytes"] / shipped) if shipped else 1.0,
    }
