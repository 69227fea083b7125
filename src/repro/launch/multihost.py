"""Multi-host bring-up for the execution substrate (DESIGN §12).

Two halves:

``init_from_env`` / ``ensure_initialized``
    Idempotent ``jax.distributed`` initialization from the coordinator /
    process-count / process-id triple, read from explicit arguments or the
    ``ADHASH_COORDINATOR`` / ``ADHASH_NUM_PROCESSES`` / ``ADHASH_PROCESS_ID``
    environment protocol.  Must run before any jax backend use in the
    process: on CPU the cross-process collectives need the gloo
    implementation, and that flag is only read at client creation.  With no
    coordinator configured this is a no-op and the process stays
    single-host — ``DistributedSubstrate`` then degenerates to
    ``MeshSubstrate`` over the local devices.

``launch_localhost`` / ``python -m repro.launch``
    A test/bench launcher that spawns N worker processes on localhost, each
    with its own block of ``--xla_force_host_platform_device_count`` fake
    CPU devices (``JAX_PLATFORMS=cpu`` in each worker's env, so the workers
    are CPU rehearsals and never compete for an accelerator), wires the env protocol (one free coordinator port, dense
    process ids) and collects per-process exit codes and output.  Workers
    re-enter through ``python -m repro.launch --worker <target>``, which
    initializes jax.distributed *before* importing the target script — the
    same ordering a real cluster launcher (SLURM, GKE) provides.

The launcher is intentionally synchronous and stdio-captured: the CI
multihost job and ``bench_startup``'s scale sweep both parse marker lines
from process 0's stdout.
"""
from __future__ import annotations

import os
import socket
import subprocess
import sys
from dataclasses import dataclass
from pathlib import Path

__all__ = [
    "init_from_env",
    "ensure_initialized",
    "launch_localhost",
    "ProcResult",
    "ENV_COORDINATOR",
    "ENV_NUM_PROCESSES",
    "ENV_PROCESS_ID",
]

ENV_COORDINATOR = "ADHASH_COORDINATOR"
ENV_NUM_PROCESSES = "ADHASH_NUM_PROCESSES"
ENV_PROCESS_ID = "ADHASH_PROCESS_ID"

_initialized = False


def init_from_env(
    *,
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Initialize ``jax.distributed`` from args or the env protocol.

    Returns True when the process joined (or had already joined) a
    multi-process mesh, False when no coordinator is configured (single
    process).  Idempotent: a second call with the same configuration is a
    no-op, so ``DistributedSubstrate`` can call this defensively even when
    the launcher already did."""
    global _initialized
    if _initialized:
        return True
    coordinator = coordinator or os.environ.get(ENV_COORDINATOR)
    if num_processes is None:
        raw = os.environ.get(ENV_NUM_PROCESSES)
        num_processes = int(raw) if raw else None
    if process_id is None:
        raw = os.environ.get(ENV_PROCESS_ID)
        process_id = int(raw) if raw is not None and raw != "" else None
    if not coordinator or not num_processes or num_processes <= 1:
        return False
    if process_id is None:
        raise ValueError(
            f"{ENV_PROCESS_ID} / process_id required when a coordinator is "
            f"configured ({coordinator!r}, {num_processes} processes)"
        )

    import jax

    # CPU collectives need gloo; the flag is consumed at backend creation,
    # which is why this function must run before any jax device use.  Older
    # jax without the option simply ignores it (single-backend fallback).
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except (AttributeError, ValueError):  # pragma: no cover - version skew
        pass
    # gloo pairs match messages by arrival order, so two *concurrently
    # executing* programs that both carry collectives can cross wires
    # (observed as `op.preamble.length <= op.nbytes` aborts or garbage
    # sizes).  CPU async dispatch is exactly what allows that overlap —
    # e.g. the engine's deferred-IRD exchanges running in the shadow of a
    # bucket evaluation — so in multi-process CPU mode every program must
    # retire before the next one dispatches.  Purely a scheduling change:
    # overlap is a perf optimization, the barrier-before-publish semantics
    # are unchanged.
    try:
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    except (AttributeError, ValueError):  # pragma: no cover - version skew
        pass
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    return True


def ensure_initialized(
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Positional-friendly alias used by ``DistributedSubstrate``."""
    return init_from_env(
        coordinator=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


# ---------------------------------------------------------------------------
# Localhost process launcher (tests, CI multihost job, startup scale sweep)
# ---------------------------------------------------------------------------
@dataclass
class ProcResult:
    """Outcome of one launched worker process."""

    process_id: int
    returncode: int
    stdout: str
    stderr: str

    @property
    def ok(self) -> bool:
        return self.returncode == 0


def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# XLA CPU's gloo transport can abort or hang under CPU oversubscription
# (concurrent independent collectives inside one program race across the
# local partition threads; see DESIGN §12) — always loudly: a SIGABRT
# with a gloo EnforceNotMet message, peers torn down by the coordination
# service, or a kill at the launcher timeout.  A worker failure matching
# these signatures says nothing about the program under test, so
# ``launch_localhost(retries=...)`` relaunches the whole group.  A normal
# Python failure in the target (assertion, exception -> rc=1 with a
# traceback, no signature) is never retried.
_INFRA_SIGNATURES = (
    "gloo::EnforceNotMet",
    "Terminating process because the JAX distributed service",
    "coordination service",
    "DEADLINE_EXCEEDED",
)


def _is_infra_failure(results: list["ProcResult"]) -> bool:
    failed = [r for r in results if not r.ok]
    if not failed:
        return False
    if any("AssertionError" in r.stderr for r in results):
        return False
    return all(
        r.returncode < 0
        or any(sig in r.stderr for sig in _INFRA_SIGNATURES)
        for r in failed
    )


def _src_root() -> str:
    # .../src/repro/launch/multihost.py -> .../src
    return str(Path(__file__).resolve().parents[2])


def launch_localhost(
    n_processes: int,
    target_argv: list[str],
    *,
    devices_per_process: int = 4,
    timeout: float = 600.0,
    env: dict[str, str] | None = None,
    port: int | None = None,
    retries: int = 0,
) -> list[ProcResult]:
    """Spawn ``n_processes`` workers on localhost running ``target_argv``.

    ``target_argv`` is what each worker executes after joining the mesh:
    either ``["-m", "module", ...args]`` or ``["script.py", ...args]``.
    Each worker gets ``devices_per_process`` fake CPU devices (appended to
    its ``XLA_FLAGS``), the env protocol above, and ``src/`` on its
    PYTHONPATH.  Blocks until every worker exits or the timeout fires; on
    timeout all workers are killed and the partial results carry returncode
    -9.  ``retries`` relaunches the whole group (fresh coordinator port)
    when the failure matches a known transport-infrastructure signature —
    never when the target itself raised."""
    if n_processes < 1:
        raise ValueError("n_processes must be >= 1")
    for _attempt in range(retries):
        results = _launch_once(n_processes, target_argv, devices_per_process,
                               timeout, env, port)
        if not _is_infra_failure(results):
            return results
    return _launch_once(n_processes, target_argv, devices_per_process,
                        timeout, env, port)


def _launch_once(
    n_processes: int,
    target_argv: list[str],
    devices_per_process: int,
    timeout: float,
    env: dict[str, str] | None,
    port: int | None,
) -> list[ProcResult]:
    port = port or _free_port()
    procs: list[subprocess.Popen] = []
    for pid in range(n_processes):
        penv = dict(os.environ)
        if env:
            penv.update(env)
        penv[ENV_COORDINATOR] = f"127.0.0.1:{port}"
        penv[ENV_NUM_PROCESSES] = str(n_processes)
        penv[ENV_PROCESS_ID] = str(pid)
        penv["JAX_PLATFORMS"] = "cpu"
        penv["XLA_FLAGS"] = (
            penv.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={devices_per_process}"
        ).strip()
        src = _src_root()
        pp = penv.get("PYTHONPATH", "")
        if src not in pp.split(os.pathsep):
            penv["PYTHONPATH"] = f"{src}{os.pathsep}{pp}" if pp else src
        cmd = [sys.executable, "-m", "repro.launch", "--worker"] + list(
            target_argv
        )
        procs.append(
            subprocess.Popen(
                cmd,
                env=penv,
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    results: list[ProcResult] = []
    try:
        for pid, p in enumerate(procs):
            out, err = p.communicate(timeout=timeout)
            results.append(ProcResult(pid, p.returncode, out, err))
    except subprocess.TimeoutExpired:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for pid, p in enumerate(procs[len(results):], start=len(results)):
            out, err = p.communicate()
            results.append(ProcResult(pid, -9, out, err))
    return results
