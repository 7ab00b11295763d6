"""Beyond-paper benchmark: the selection protocols' communication cost in
*compiled HLO collective bytes* — the mesh-native restatement of Fig. 2/9
— plus the mesh-sharded selection prefix's per-device scaling.

Runs in a subprocess with forced host devices (so collectives
materialize).  ``bench_selection_collectives`` compares per-device
collective bytes of:
  - ccs_state_gather  (full state vector to the server)  ~ O(N * state_dim)
  - ccs_fuzzy_gather  (scalar evaluations to the server)  ~ O(N)
  - dcs_neighbor_exchange (boundary window to 2 neighbours) ~ O(window)

``bench_prefix_sharding`` runs ``selection_prefix_sharded`` at a fixed
fleet size on 1/2/4/8-device client meshes and records the *measured*
per-device bytes of the client-axis arrays (statics shards + packed
probe region, via ``addressable_shards``) and the prefix wall time —
the per-device client-axis memory must shrink ~1/K with mesh size.

``bench_windowed_scaling`` (ISSUE 9) is the N-scaling curve of the
windowed neighbour-exchange election vs the dense full-gather seam, at
fixed vehicle density (road length grows with N) on a 16-device mesh,
N up to 10^6 emulated vehicles:

- per-device collective bytes split by kind from compiled HLO — the
  halo ``collective-permute`` bytes must stay FLAT in N (the window is
  density-determined), while the full gather's ``all-gather`` bytes
  grow O(N); the bucketing ``all-to-all`` is O(N/K) layout movement
  and is reported separately, never folded into the halo number;
- measured election wall time for the windowed path up to
  ``REPRO_WINDOWED_MAXN`` (the dense gather election is O(N^2) compute
  and only executes at the smallest N, where the windowed mask is also
  asserted bit-identical to the dense reference);
- CI gates: halo bytes flat (max/min < 1.6) and windowed total bytes
  under the gather bytes at the largest executed N.

Results append to the cumulative ``BENCH_selection.json`` artifact
(profile "windowed-scaling") alongside the prefix-fusion trajectory.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, List

_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import json
import jax
import jax.numpy as jnp
from repro.core.fuzzy import FuzzyEvaluator
from repro.core.protocol import (make_ccs_fuzzy_gather, make_ccs_state_gather,
                                 make_dcs_neighbor_exchange)
from repro.launch import hlo_cost

mesh = jax.make_mesh((16,), ("data",))
N, SD, WIN = 1_048_576, 25, 1024       # 1M vehicles, 25-float state
states = jax.ShapeDtypeStruct((N, SD), jnp.float32)
ev = jax.ShapeDtypeStruct((N,), jnp.float32)
pos = jax.ShapeDtypeStruct((N,), jnp.float32)

out = {}
g = jax.jit(make_ccs_state_gather(mesh, FuzzyEvaluator(), 1000, SD)) \
    .lower(states).compile()
out["ccs_state_gather"] = hlo_cost.analyze(g.as_text()).collective_bytes
f = jax.jit(make_ccs_fuzzy_gather(mesh, 1000)).lower(ev).compile()
out["ccs_fuzzy_gather"] = hlo_cost.analyze(f.as_text()).collective_bytes
d = jax.jit(make_dcs_neighbor_exchange(mesh, comm_range=200.0, top_m=2,
                                       e_tau=30.0, window=WIN)) \
    .lower(pos, ev).compile()
out["dcs_neighbor_exchange"] = hlo_cost.analyze(d.as_text()).collective_bytes
print(json.dumps(out))
"""


def bench_selection_collectives() -> List[str]:
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD], capture_output=True, text=True,
        env={**__import__("os").environ, "PYTHONPATH": "src"}, timeout=540)
    if proc.returncode != 0:
        return [f"selection_collectives_error,1,{proc.stderr[-200:]!r}"]
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    rows = []
    for k, v in data.items():
        rows.append(f"collective_bytes_{k},{v:.3e},per-device;N=1048576")
    if data["dcs_neighbor_exchange"] > 0:
        ratio = data["ccs_state_gather"] / data["dcs_neighbor_exchange"]
        rows.append(f"collective_ratio_ccs_over_dcs,{ratio:.1f},"
                    "Eq.5 elimination, in compiled HLO bytes")
    return rows


_CHILD_PREFIX = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import time
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.mnist_cnn import CONFIG as CNN_CFG
from repro.core.fuzzy import FuzzyEvaluator, FuzzyEvaluatorConfig
from repro.fl import pipeline
from repro.fl.network import NetworkConfig
from repro.fl.timing import TimingConfig
from repro.launch.mesh import make_clients_mesh
from repro.models.cnn import init_cnn

N, B, PER = 256, 64, 64            # clients, probe batch, samples/client
rng = np.random.default_rng(0)
ev = FuzzyEvaluator(FuzzyEvaluatorConfig())
f32 = jnp.float32
S = N * PER                        # one whole probe batch per client
st = pipeline.RoundStatics(
    x0=jnp.asarray(rng.uniform(0, 1000.0, N), f32),
    speeds=jnp.asarray(rng.uniform(20, 33, N), f32),
    jitter_phase=jnp.asarray(rng.uniform(0, 6.28, N), f32),
    slowdown=jnp.asarray(rng.uniform(1, 4, N), f32),
    n_valid=jnp.asarray(np.full(N, PER), f32),
    probe_images=jnp.asarray(
        rng.normal(size=(S, 28, 28, 1)).astype(np.float32)),
    probe_labels=jnp.asarray(rng.integers(0, 10, S).astype(np.int32)),
    probe_seg=jnp.asarray(np.repeat(np.arange(N), PER).astype(np.int32)),
    probe_counts=jnp.asarray(np.full(N, PER, np.int32)),
    means=jnp.asarray(ev.cfg.means, f32),
    sigmas=jnp.asarray(ev.cfg.sigmas, f32),
    level_centers=jnp.asarray(ev.level_centers, f32))
cfg = pipeline.StageConfig(
    scheme="dcs", n_clients=N, comm_range_m=200.0, top_m=2, e_tau=30.0,
    n_clients_central=5, model_bytes=5.2e6, road_length_m=1000.0,
    speed_jitter=1.0, timing=TimingConfig(epochs=1, batch_size=20,
                                          deadline_s=60.0),
    network=NetworkConfig(), probe_batch=B)
params = init_cnn(jax.random.PRNGKey(0), CNN_CFG)
key = jax.random.PRNGKey(1)
net_key = jax.random.PRNGKey(2)

# the client-axis arrays the prefix shards, with their partition specs
CLIENT_LEAVES = [
    (st.x0, P("clients")), (st.speeds, P("clients")),
    (st.jitter_phase, P("clients")), (st.slowdown, P("clients")),
    (st.n_valid, P("clients")),
    (st.probe_images, P("clients", None, None, None)),
    (st.probe_labels, P("clients")), (st.probe_seg, P("clients")),
]

out = {}
for k in (1, 2, 4, 8):
    mesh = make_clients_mesh(k)
    per_dev = {}
    for arr, spec in CLIENT_LEAVES:
        sharded = jax.device_put(arr, NamedSharding(mesh, spec))
        for sh in sharded.addressable_shards:
            per_dev[sh.device.id] = (per_dev.get(sh.device.id, 0)
                                     + sh.data.nbytes)
    res = pipeline.selection_prefix_sharded(
        st, params, jnp.int32(0), key, net_key, cfg=cfg, mesh=mesh)
    jax.block_until_ready(res)                     # compile
    reps = 3
    t0 = time.perf_counter()
    for r in range(1, reps + 1):
        jax.block_until_ready(pipeline.selection_prefix_sharded(
            st, params, jnp.int32(r), key, net_key, cfg=cfg, mesh=mesh))
    out[str(k)] = {"bytes_per_device": max(per_dev.values()),
                   "wall_ms": (time.perf_counter() - t0) / reps * 1e3,
                   "n_selected": int(res["n_selected"])}
out["platform"] = jax.devices()[0].platform
print(json.dumps(out))
"""


def bench_prefix_sharding() -> List[str]:
    # raise (-> benchmarks/run.py exits nonzero) instead of an error row:
    # the CI test-sharded step gates on this bench, so a crashed sharded
    # prefix or a silently-replicated client axis must fail the job
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD_PREFIX], capture_output=True,
        text=True, env={**__import__("os").environ, "PYTHONPATH": "src"},
        timeout=540)
    if proc.returncode != 0:
        raise RuntimeError(
            f"prefix_sharding child failed:\n{proc.stderr[-2000:]}")
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    platform = data.pop("platform")
    rows = []
    for k, d in sorted(data.items(), key=lambda kv: int(kv[0])):
        rows.append(f"prefix_clientaxis_bytes_per_device_k{k},"
                    f"{d['bytes_per_device']:.3e},"
                    f"N=256;64 probe samples/client")
        rows.append(f"prefix_wall_ms_k{k},{d['wall_ms']:.1f},"
                    f"sharded selection prefix, {k} emulated devices;"
                    f"platform={platform}")
    shrink = (data["1"]["bytes_per_device"]
              / max(data["8"]["bytes_per_device"], 1))
    if shrink < 4.0:                     # exact split measures 8.0
        raise RuntimeError(
            f"per-device client-axis memory shrank only {shrink:.2f}x "
            f"from 1 to 8 shards — the client partition is replicating")
    rows.append(f"prefix_clientaxis_shrink_1_to_8,{shrink:.2f},"
                "per-device client-axis memory ratio (want ~8)")
    return rows


_CHILD_WINDOWED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import time
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import elect as celect
from repro.kernels import ref as kref
from repro.launch import hlo_cost
from repro.launch.mesh import make_clients_mesh
from repro.sharding.api import CLIENT_AXIS

K = 16
CR, TOP_M, E_TAU = 200.0, 2, 30.0
WALL_MAXN = int(os.environ.get("REPRO_WINDOWED_MAXN", "262144"))
BYTES_MAXN = 1_048_576
NS = [n for n in (4096, 16384, 65536, 262144, 1_048_576)
      if n <= max(BYTES_MAXN, WALL_MAXN)]
mesh = make_clients_mesh(K)
sh = NamedSharding(mesh, P(CLIENT_AXIS))


def windowed_fn(n, road, window, cap):
    shard_n = n // K

    def f(pos, ev, gid, valid):
        mask, ovf = celect.ring_halo_elect(
            pos, ev, gid, valid, axis=CLIENT_AXIS, n=n, n_shards=K,
            shard_n=shard_n, comm_range=CR, top_m=TOP_M, e_tau=E_TAU,
            road_length=road, window=window, capacity=cap)
        return mask, jax.lax.pmax(ovf, CLIENT_AXIS)

    return jax.jit(jax.shard_map(f, mesh=mesh,
                             in_specs=(P(CLIENT_AXIS),) * 4,
                             out_specs=(P(CLIENT_AXIS), P())))


def gather_bytes_fn(n):
    # the dense seam's collectives alone (the O(N^2) election compute is
    # omitted so the function stays compilable/executable at any N — the
    # all_gather bytes are what the windowed path eliminates)
    shard_n = n // K

    def f(pos, ev):
        pg = jax.lax.all_gather(pos, CLIENT_AXIS, tiled=True)
        eg = jax.lax.all_gather(ev, CLIENT_AXIS, tiled=True)
        i = jax.lax.axis_index(CLIENT_AXIS)
        merged = pg + eg                 # consume both gathers
        return jax.lax.dynamic_slice_in_dim(merged, i * shard_n, shard_n)

    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P(CLIENT_AXIS),) * 2,
                             out_specs=P(CLIENT_AXIS)))


def gather_elect_fn(n):
    # the real dense election on gathered vectors (wall-clock reference;
    # O(N^2) — executed at the smallest N only)
    shard_n = n // K

    def f(pos, ev):
        pg = jax.lax.all_gather(pos, CLIENT_AXIS, tiled=True)
        eg = jax.lax.all_gather(ev, CLIENT_AXIS, tiled=True)
        mask = kref.neighbor_elect_ref(pg, eg, comm_range=CR, top_m=TOP_M,
                                       e_tau=E_TAU)
        i = jax.lax.axis_index(CLIENT_AXIS)
        return jax.lax.dynamic_slice_in_dim(mask, i * shard_n, shard_n)

    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P(CLIENT_AXIS),) * 2,
                             out_specs=P(CLIENT_AXIS)))


def kind_bytes(compiled):
    cost = hlo_cost.analyze(compiled.as_text())
    return {"total": cost.collective_bytes, **cost.by_kind}


out = {}
rng = np.random.default_rng(0)
for n in NS:
    road = float(n)                      # fixed density: 1 vehicle / m
    window = celect.auto_window(n, CR, road)
    cap = celect.auto_capacity(n // K, K)
    pos_np = rng.uniform(0.0, road, n).astype(np.float32)
    ev_np = rng.uniform(0.0, 100.0, n).astype(np.float32)
    shapes = (jax.ShapeDtypeStruct((n,), jnp.float32),
              jax.ShapeDtypeStruct((n,), jnp.float32),
              jax.ShapeDtypeStruct((n,), jnp.int32),
              jax.ShapeDtypeStruct((n,), jnp.bool_))
    wfn = windowed_fn(n, road, window, cap)
    wc = wfn.lower(*shapes).compile()
    gc = gather_bytes_fn(n).lower(*shapes[:2]).compile()
    rec = {"window": window, "capacity": cap,
           "windowed": kind_bytes(wc), "gather": kind_bytes(gc)}
    if n <= WALL_MAXN:                   # execute the windowed election
        args = (jax.device_put(pos_np, sh), jax.device_put(ev_np, sh),
                jax.device_put(np.arange(n, dtype=np.int32), sh),
                jax.device_put(np.ones(n, np.bool_), sh))
        mask, ovf = wc(*args)
        jax.block_until_ready(mask)
        reps = 3
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(wc(*args)[0])
        rec["windowed_wall_ms"] = (time.perf_counter() - t0) / reps * 1e3
        rec["overflow"] = int(ovf)
    if n == NS[0] and "overflow" in rec:  # dense ref: wall + parity
        ge = gather_elect_fn(n).lower(*shapes[:2]).compile()
        mask_ref = ge(args[0], args[1])
        jax.block_until_ready(mask_ref)
        t0 = time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(ge(args[0], args[1]))
        rec["gather_wall_ms"] = (time.perf_counter() - t0) / 3 * 1e3
        if rec["overflow"] == 0 and not bool(
                np.array_equal(np.asarray(mask), np.asarray(mask_ref))):
            raise SystemExit("windowed mask != dense election at N=%d "
                             "with overflow=0" % n)
        rec["parity_checked"] = int(rec["overflow"] == 0)
    out[str(n)] = rec
out["platform"] = jax.devices()[0].platform
print(json.dumps(out))
"""


def _append_selection_artifact(profile: str, cells: List[Dict]) -> None:
    path = os.environ.get("REPRO_BENCH_SELECTION_OUT",
                          "BENCH_selection.json")
    data = {"runs": []}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (json.JSONDecodeError, OSError):
            data = {"runs": []}
    data.setdefault("runs", []).append(
        {"unix_time": int(time.time()), "profile": profile, "cells": cells})
    # atomic append-rewrite: a killed bench never tears the cumulative
    # artifact (repro.ioutil, ISSUE 10)
    from repro.ioutil import write_atomic_json
    write_atomic_json(path, data, indent=1)


def bench_windowed_scaling() -> List[str]:
    """Windowed-vs-gather election scaling (raises on a lost gate so CI
    fails the job, same policy as ``bench_prefix_sharding``)."""
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD_WINDOWED], capture_output=True,
        text=True, env={**os.environ, "PYTHONPATH": "src"}, timeout=3000)
    if proc.returncode != 0:
        raise RuntimeError(
            f"windowed_scaling child failed:\n{proc.stderr[-2000:]}\n"
            f"{proc.stdout[-500:]}")
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    platform = data.pop("platform")
    rows, cells = [], []
    halo, executed = {}, []
    for n_s, rec in sorted(data.items(), key=lambda kv: int(kv[0])):
        n = int(n_s)
        wb, gb = rec["windowed"], rec["gather"]
        halo[n] = wb.get("collective-permute", 0.0)
        rows.append(f"windowed_halo_bytes_n{n},{halo[n]:.3e},"
                    f"per-device ppermute halo; window={rec['window']}")
        rows.append(f"windowed_a2a_bytes_n{n},"
                    f"{wb.get('all-to-all', 0.0):.3e},"
                    f"per-device bucketing layout movement (O(N/K))")
        rows.append(f"windowed_total_bytes_n{n},{wb['total']:.3e},"
                    f"per-device, all collectives")
        rows.append(f"gather_bytes_n{n},{gb['total']:.3e},"
                    f"per-device dense-seam all_gather (O(N))")
        if "windowed_wall_ms" in rec:
            executed.append(n)
            rows.append(f"windowed_elect_wall_ms_n{n},"
                        f"{rec['windowed_wall_ms']:.1f},"
                        f"16 emulated devices; overflow="
                        f"{rec['overflow']};platform={platform}")
        if "gather_wall_ms" in rec:
            rows.append(f"gather_elect_wall_ms_n{n},"
                        f"{rec['gather_wall_ms']:.1f},"
                        f"dense O(N^2) election on gathered vectors;"
                        f"platform={platform}")
        cells.append({"n": n, **rec})
    # gate 1: halo bytes flat in N at fixed density (the whole point —
    # the exchanged window is determined by density, not fleet size)
    hi, lo = max(halo.values()), max(min(halo.values()), 1.0)
    rows.append(f"windowed_halo_flatness,{hi / lo:.3f},"
                "max/min per-device halo bytes across N (want ~1)")
    if hi / lo >= 1.6:
        raise RuntimeError(
            f"halo bytes grew {hi / lo:.2f}x across N — the neighbour "
            f"exchange is not O(window) per device")
    # gate 2: the win at the largest executed N — total windowed bytes
    # (bucketing included) under the dense seam's gather bytes
    gate_n = max(executed)
    wt = data[str(gate_n)]["windowed"]["total"]
    gt = data[str(gate_n)]["gather"]["total"]
    rows.append(f"windowed_bytes_win_n{gate_n},{gt / max(wt, 1.0):.2f},"
                "gather/windowed per-device collective bytes (want > 1)")
    if wt >= gt:
        raise RuntimeError(
            f"windowed election moved {wt:.3e} collective B/device at "
            f"N={gate_n}, not under the gather seam's {gt:.3e}")
    _append_selection_artifact("windowed-scaling", cells)
    return rows
