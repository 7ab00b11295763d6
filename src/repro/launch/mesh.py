"""Production mesh construction (TPU v5e pods; 256 chips/pod) plus the
FL launchers' ``clients`` mesh.

Defined as functions — importing this module never touches jax device
state.  The dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count
=512 *before* any jax import to build these meshes on CPU; the FL
launchers (``fl_sim``/``sweep`` with ``--mesh clients=K``) do the same
through ``ensure_host_device_count`` before their first jax operation.
"""
from __future__ import annotations

import contextlib
import os
from typing import Dict, Optional, Tuple

import numpy as np

import jax
from jax.sharding import Mesh

from repro.sharding.api import CLIENT_AXIS


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(n_devices: int = 0, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests)."""
    n = n_devices or len(jax.devices())
    if model < 1 or n % model != 0:
        raise ValueError(
            f"cannot build a debug mesh: {n} devices not divisible by "
            f"model={model}")
    return jax.make_mesh((n // model, model), ("data", "model"))


def make_clients_mesh(n_shards: int = 0) -> Mesh:
    """1-D ``("clients",)`` mesh over the first ``n_shards`` local devices
    — the launcher's ``--mesh clients=K``.  ``0`` takes every device."""
    devices = jax.devices()
    n = n_shards or len(devices)
    if n < 1:
        raise ValueError(f"clients mesh needs >= 1 shard, got {n}")
    if n > len(devices):
        raise ValueError(
            f"clients mesh wants {n} devices but only {len(devices)} "
            f"exist; on CPU, relaunch with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}")
    return Mesh(np.asarray(devices[:n]), (CLIENT_AXIS,))


def init_distributed(coordinator: str, num_processes: int,
                     process_id: int, local_devices: int = 1) -> None:
    """Join a multi-process jax runtime (``--multihost`` children).

    Must run before the first jax operation: the host-device count flag
    and the CPU collectives backend are only read at backend init.  On
    CPU, cross-process collectives go through gloo; each process
    contributes ``local_devices`` emulated host devices, so the global
    device count is ``num_processes * local_devices``.

    Robustness (ISSUE 10): the barrier-at-init is where a dead or
    never-started peer used to hang a launch forever.  The init now runs
    under a hard timeout (``REPRO_DIST_TIMEOUT_S``, default 60s) with
    bounded retries + backoff (``REPRO_DIST_INIT_ATTEMPTS``, default 3),
    and the terminal error names this rank and the coordinator."""
    from repro.launch.multihost import retry_with_backoff
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{local_devices}".strip())
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    timeout_s = int(float(os.environ.get("REPRO_DIST_TIMEOUT_S", "60")))
    attempts = int(os.environ.get("REPRO_DIST_INIT_ATTEMPTS", "3"))

    def _init():
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes, process_id=process_id,
            initialization_timeout=timeout_s)

    retry_with_backoff(
        _init, attempts=attempts,
        desc=(f"jax.distributed init (rank {process_id}/{num_processes} "
              f"via {coordinator})"))


def make_multihost_clients_mesh(n_shards: int) -> Mesh:
    """1-D ``("clients",)`` mesh over the GLOBAL device list of an
    initialized multi-process runtime.  ``jax.devices()`` orders global
    devices by (process_index, local id), so shard ``d`` lives on
    process ``d // (K / P)`` — the per-host client-loading seam in
    ``fl/rounds.py`` relies on that contiguity."""
    devices = jax.devices()
    if n_shards != len(devices):
        raise ValueError(
            f"multihost clients mesh wants clients={n_shards} but the "
            f"distributed runtime exposes {len(devices)} global devices "
            f"({jax.process_count()} processes x "
            f"{len(jax.local_devices())} local)")
    return Mesh(np.asarray(devices), (CLIENT_AXIS,))


def parse_mesh_spec(spec: str) -> Dict[str, int]:
    """``"clients=8"`` (comma-separable) -> ``{"clients": 8}``."""
    out: Dict[str, int] = {}
    for part in spec.split(","):
        name, _, val = part.partition("=")
        name = name.strip()
        if not name or not val:
            raise ValueError(f"bad mesh axis {part!r} (want axis=N)")
        try:
            out[name] = int(val)
        except ValueError:
            raise ValueError(f"bad mesh extent {val!r} for axis {name!r}")
    return out


@contextlib.contextmanager
def client_mesh_context(spec: Optional[str],
                        multihost: Optional[Tuple[str, int, int]] = None):
    """``--mesh`` handling shared by the FL launchers: ``"clients=K"``
    builds the K-way clients mesh (forcing K emulated CPU host devices
    when the backend has not initialized yet) and activates it plus the
    logical sharding rules for every simulation constructed inside.
    ``None``/empty is a no-op single-device context.

    ``multihost=(coordinator, num_processes, process_id)`` — a spawned
    ``--multihost`` child — first joins the distributed runtime; the
    spec's ``clients=K`` is then the GLOBAL extent (``K %%
    num_processes == 0``, each process contributing ``K / P`` emulated
    devices) and the mesh spans every process."""
    if multihost is not None:
        coord, procs, pid = multihost
        if not spec:
            raise ValueError("--multihost needs --mesh clients=K (the "
                             "client axis is what spans the processes)")
        axes = parse_mesh_spec(spec)
        k = axes.get(CLIENT_AXIS, 1)
        if procs < 1 or k % procs != 0:
            raise ValueError(
                f"--mesh clients={k} must divide evenly over "
                f"--multihost {procs} processes")
        # chaos hook: kill one rank before it joins the barrier, so the
        # parent's peer-death reaping (spawn_multihost) is exercised
        from repro.launch import faults
        faults.fire("mh-child-start", rank=pid)
        init_distributed(coord, procs, pid, local_devices=k // procs)
        mesh = make_multihost_clients_mesh(k)
        from repro.sharding.api import DEFAULT_RULES, logical_sharding
        with mesh, logical_sharding(mesh, DEFAULT_RULES):
            yield mesh
        return
    if not spec:
        yield None
        return
    axes = parse_mesh_spec(spec)
    unknown = sorted(set(axes) - {CLIENT_AXIS})
    if unknown:
        raise ValueError(f"unknown mesh axes {unknown} (the FL launchers "
                         f"only partition {CLIENT_AXIS!r})")
    k = axes.get(CLIENT_AXIS, 1)
    if k > 1:
        ensure_host_device_count(k)
    mesh = make_clients_mesh(k)
    from repro.sharding.api import DEFAULT_RULES, logical_sharding
    with mesh, logical_sharding(mesh, DEFAULT_RULES):
        yield mesh


def ensure_host_device_count(n: int) -> None:
    """Best-effort CPU host-device emulation for ``--mesh clients=K``.

    Appends ``--xla_force_host_platform_device_count=N`` to XLA_FLAGS —
    effective only if the jax backend has not initialized yet, which is
    why the launchers call this before their first jax operation.  If the
    devices still do not materialize (backend already live, or a real
    accelerator platform), raises with the relaunch recipe instead of
    quietly running single-device."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"requested {n} devices but only {len(jax.devices())} "
            f"materialized (jax backend already initialized?); set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} in the "
            f"environment before launching")


# v5e hardware constants for the roofline (per chip / per link)
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # B/s
ICI_BW_PER_LINK = 50e9            # B/s
CHIPS_PER_POD = 256
HBM_BYTES_PER_CHIP = 16 * 1024**3
