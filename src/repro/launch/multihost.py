"""Multi-process launch plumbing (jax-free: argparse + subprocess only).

``--multihost P`` runs a launcher as ``P`` cooperating jax processes —
a CPU *emulation* of a multi-host fleet: ``P`` copies of the same
command wired to one local coordinator, each owning ``K / P`` emulated
host devices of the ``clients`` mesh, with gloo CPU collectives.  On
any other backend the launch is refused (``require_cpu_backend``): a
chip belongs to one process, so ``P`` local processes cannot share it.

The spawn protocol is self-re-execution: the parent parses
``--multihost P``, picks a free coordinator port, and re-launches its
own ``python -m <module> <argv>`` ``P`` times with the hidden
``--_mh-coord/--_mh-procs/--_mh-proc-id`` flags appended; a child sees
``--_mh-proc-id`` and initializes ``jax.distributed`` instead of
re-spawning.  Output-writing call sites gate on ``jax.process_index()
== 0``.  This module stays importable before jax so launchers can parse
flags without initializing any backend.
"""
from __future__ import annotations

import socket
import subprocess
import sys
import time
from typing import Callable, List, Optional, Sequence, Tuple


def retry_with_backoff(fn: Callable, *, attempts: int = 3,
                       base_delay_s: float = 1.0,
                       desc: str = "operation"):
    """Call ``fn()`` with bounded retries and exponential backoff
    (1x, 2x, 4x ... ``base_delay_s``).  The final failure re-raises the
    last error wrapped with ``desc`` and the attempt count, so a
    flaky-but-fatal init (a peer that never comes up) reports what was
    being retried instead of a bare timeout."""
    last: Optional[BaseException] = None
    for attempt in range(attempts):
        try:
            return fn()
        except Exception as e:          # noqa: BLE001 — re-raised below
            last = e
            if attempt + 1 < attempts:
                delay = base_delay_s * (2 ** attempt)
                print(f"[multihost] {desc} failed "
                      f"(attempt {attempt + 1}/{attempts}): {e}; "
                      f"retrying in {delay:.0f}s", file=sys.stderr,
                      flush=True)
                time.sleep(delay)
    raise RuntimeError(
        f"{desc} failed after {attempts} attempts: {last}") from last


def add_multihost_arguments(ap) -> None:
    """Install ``--multihost`` plus the hidden child-process flags."""
    ap.add_argument("--multihost", type=int, default=0, metavar="P",
                    help="run as P cooperating jax processes (a CPU "
                         "emulation via spawned local processes); the "
                         "mesh's clients=K axis spans all of them "
                         "(K %% P == 0)")
    ap.add_argument("--_mh-coord", default=None, help=_SUPPRESS())
    ap.add_argument("--_mh-procs", type=int, default=None,
                    help=_SUPPRESS())
    ap.add_argument("--_mh-proc-id", type=int, default=None,
                    help=_SUPPRESS())


def _SUPPRESS() -> str:
    import argparse
    return argparse.SUPPRESS


def multihost_from_args(args) -> Optional[Tuple[str, int, int]]:
    """The child-process distributed-init triple ``(coordinator,
    num_processes, process_id)``, or None outside a spawned child."""
    pid = getattr(args, "_mh_proc_id", None)
    if pid is None:
        return None
    return (args._mh_coord, int(args._mh_procs), int(pid))


def should_spawn(args) -> bool:
    """True in the parent process of a ``--multihost P`` launch (P > 1
    and not already a spawned child)."""
    return (getattr(args, "multihost", 0) or 0) > 1 \
        and getattr(args, "_mh_proc_id", None) is None


def require_cpu_backend(what: str) -> None:
    """Refuse a launch mode that starts several jax processes on this
    host unless the backend is the CPU.  A TPU chip belongs to one
    process at a time: a parent holding it leaves its children failing
    or hanging, and sibling children cannot share it either."""
    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        raise SystemExit(
            f"{what} starts several jax processes on this host, but the "
            f"{backend} backend gives each chip to one process; run "
            f"without it (one process drives every local chip)")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _reap(procs: Sequence[subprocess.Popen],
          grace_s: float = 5.0) -> None:
    """Terminate (then kill) every still-running child."""
    for p in procs:
        if p.poll() is None:
            p.terminate()
    deadline = time.monotonic() + grace_s
    for p in procs:
        if p.poll() is None:
            try:
                p.wait(timeout=max(0.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()


def spawn_multihost(module: str, argv: Sequence[str], nprocs: int,
                    *, timeout: Optional[float] = None,
                    poll_s: float = 0.2) -> int:
    """Re-launch ``python -m module argv`` as ``nprocs`` coordinated
    child processes and wait.  Child 0 streams to the parent's
    stdout/stderr (it owns all output writes); the others keep stderr
    for crash visibility but drop stdout.  Returns the max exit code.

    Failure containment (ISSUE 10): the parent *polls* the whole fleet
    instead of joining rank by rank — when any peer dies with a nonzero
    status the survivors are reaped immediately (a dead rank would
    otherwise leave the rest blocked in a collective forever) and the
    error names the dead rank.  ``timeout`` bounds the whole launch the
    same way (exit code 124, like timeout(1))."""
    require_cpu_backend(f"--multihost {nprocs}")
    coord = f"127.0.0.1:{free_port()}"
    procs: List[subprocess.Popen] = []
    for pid in range(nprocs):
        cmd = [sys.executable, "-m", module, *argv,
               "--_mh-coord", coord, "--_mh-procs", str(nprocs),
               "--_mh-proc-id", str(pid)]
        procs.append(subprocess.Popen(
            cmd, stdout=None if pid == 0 else subprocess.DEVNULL))
    deadline = (time.monotonic() + timeout) if timeout else None

    def norm(c: int) -> int:
        # shell convention: death by signal S reports 128 + S, so a
        # SIGKILLed rank can never masquerade as success through max()
        return c if c >= 0 else 128 - c

    try:
        while True:
            codes = [p.poll() for p in procs]
            if all(c is not None for c in codes):
                return max(norm(c) for c in codes)
            dead = [(rank, c) for rank, c in enumerate(codes)
                    if c is not None and c != 0]
            if dead:
                rank, code = dead[0]
                what = (f"signal {-code}" if code < 0
                        else f"exit code {code}")
                print(f"[multihost] rank {rank}/{nprocs} died with "
                      f"{what}; reaping the surviving processes",
                      file=sys.stderr, flush=True)
                _reap(procs)
                # report the rank(s) that died on their own — the
                # survivors we just SIGTERMed would otherwise mask the
                # root cause with their 143s
                return max(norm(c) for _, c in dead)
            if deadline is not None and time.monotonic() > deadline:
                print(f"[multihost] launch exceeded {timeout:.0f}s; "
                      f"reaping all {nprocs} processes",
                      file=sys.stderr, flush=True)
                _reap(procs)
                return 124
            time.sleep(poll_s)
    finally:
        _reap(procs)
