"""Event-driven streaming FL server (ISSUE 6 tentpole).

The synchronous drivers in ``fl/rounds.py`` aggregate at a round
barrier: every selected client either lands inside the Eq. 6 deadline
or is discarded.  This module generalizes the PR 5 round-ahead
scheduler into an **event-driven fleet**:

- **churn**: the staged prefix (``fl/pipeline.py``) gates evaluation /
  selection on a mobility-driven coverage window
  (``mobility.coverage_active``) and reports each client's presence at
  its own upload-completion instant — a vehicle that leaves RSU
  coverage mid-training loses its pending update;
- **staleness**: with ``staleness="weighted"`` stragglers past the
  deadline still train; their update lands at a later aggregation tick
  with FedAvg weight scaled by ``timing.staleness_weight`` —
  ``1 / (1 + lambda * delay_rounds)``;
- **cadence**: the server aggregates every ``agg_cadence_s`` simulated
  seconds (default: the round period) instead of at the round barrier.

Tick algebra (all host-side integers; ``P`` is the round period
``deadline_s``, ``T`` the cadence):

    round r spans      [r*P, (r+1)*P)
    update lands at    tick k = ceil(t_done / T)
    tick k fires in    round ceil(k*T / P) - 1
    delay_rounds       = firing round - source round   (>= 0)

Each tick's aggregation is a FedAvg over the updates landing at that
tick, plus — in weighted mode — an **anchor** row: the current global
model carrying the staleness-discounted weight mass
``sum_i w_i * (1 - s_i)``.  A fully fresh tick (every ``s_i = 1``) is
therefore plain FedAvg; a fully stale one leaves the global model
(almost) unchanged, and the update's pull shrinks continuously with
``lambda`` in between.  Drop mode never adds the anchor — it is the
``lambda -> inf`` limit pinned exactly to {1 at deadline, 0 after}.

**Sync parity**: with churn off, staleness "drop" and the cadence at
the round period, every surviving update lands at tick ``r + 1`` —
which fires in round ``r`` — so the event server degenerates to the
round barrier.  That case is detected up front and delegates training
and row assembly to ``FLSimulation`` verbatim, which (together with the
statically-gated churn branch compiling the identical prefix
executable) makes the event server reproduce the serial driver's rows
**bit-identically** (pinned in tests/test_async.py, single-device and
on a forced 4-device clients mesh).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl import pipeline, trace
from repro.fl.aggregation import fedavg_masked
from repro.fl.client import evaluate_accuracy_async
from repro.fl.rounds import (build_round_checkpointer, checkpoint_round,
                             resume_rows)
from repro.fl.timing import staleness_weight

# the pool FedAvg must NOT donate: a landing tick can merge stacks that
# were enqueued rounds ago and (in principle) share buffers with other
# ticks' pending entries, so the donated twin in fl/pipeline.py is off
# limits here
_fedavg_pool = jax.jit(lambda merged, weights: fedavg_masked(merged,
                                                             weights))

# rounds-behind histogram bins: delays 0, 1, 2, 3+ (aggregated updates)
_HIST_BINS = 4

# pending-entry scalar fields and their host types (checkpoint restore
# re-coerces through these so a JSON/npz round-trip cannot drift a type)
_ENTRY_SCALARS = {"src": int, "n": int, "delay": int,
                  "anchor": float, "scale": float}


class EventDrivenServer:
    """Streaming aggregation driver wrapping one ``FLSimulation``.

    Duck-types the simulation's driver surface (``_dispatch_training``,
    ``_round_row``, ``finish_round``, ``run``) so the sweep harness and
    the round-ahead scheduler drive it unchanged; the staged selection
    prefix — fused probe, clients-mesh sharding and all — stays on the
    wrapped simulation and keeps compiling the same executables."""

    def __init__(self, sim):
        self.sim = sim
        self.run_cfg = sim.run_cfg
        self.period = float(sim.stage_cfg.timing.deadline_s)
        self.cadence = float(self.run_cfg.agg_cadence_s
                             if self.run_cfg.agg_cadence_s is not None
                             else self.period)
        self.weighted = self.run_cfg.staleness == "weighted"
        # the degenerate event server IS the round barrier: no churn, hard
        # deadline, one tick per round -> delegate to the sync driver
        # verbatim (the bit-parity pin)
        self.sync_equivalent = (self.run_cfg.churn_rate == 0.0
                                and not self.weighted
                                and self.cadence == self.period)
        if not self.sync_equivalent and self.run_cfg.engine != "batched":
            raise ValueError(
                "the event-driven pool path trains through the batched "
                f"engine; engine={self.run_cfg.engine!r} only supports "
                "the sync-equivalent configuration")
        # landing tick -> [(source round, stack/psum partials, ...)]
        self._pending: Dict[int, List[Dict]] = {}
        self._stats: Dict[int, Dict] = {}

    # -- sweep/driver duck-typing surface ------------------------------
    @property
    def params(self):
        return self.sim.params

    @property
    def test_images(self):
        return self.sim.test_images

    @property
    def test_labels(self):
        return self.sim.test_labels

    def selection_state(self, rnd: int) -> Dict[str, jax.Array]:
        return self.sim.selection_state(rnd)

    # -- tick algebra ---------------------------------------------------
    def _tick_round(self, k: int) -> int:
        """The round in which tick ``k`` fires (k*T falls inside it)."""
        return int(math.ceil(k * self.cadence / self.period)) - 1

    def _due_ticks(self, rnd: int) -> List[int]:
        """Pending ticks firing by the end of round ``rnd``, in order."""
        k_max = int(math.floor((rnd + 1) * self.period / self.cadence))
        return sorted(k for k in self._pending if k <= k_max)

    # -- training dispatch ---------------------------------------------
    def _dispatch_training(self, rnd: int, host: Dict) -> None:
        """Enqueue round ``rnd``'s local training into landing-tick
        pools, then fire every aggregation tick due by the round's end.
        Training always starts from the *current* global model (the
        broadcast at round start), so enqueue precedes the tick sweep."""
        if self.sync_equivalent:
            self.sim._dispatch_training(rnd, host)
            return
        with trace.span(trace.COHORT, round=rnd):
            self._stats[rnd] = {"n_agg": 0, "n_stale": 0, "eff": 0.0,
                                "hist": [0] * _HIST_BINS}
            self._enqueue_round(rnd, host)
            self._process_due_ticks(rnd)

    def _enqueue_round(self, rnd: int, host: Dict) -> None:
        sim = self.sim
        cfg = sim.cfg
        mask = np.asarray(host["mask"])
        sim._record_participation(mask)
        survivors = np.asarray(host["survivors"]).astype(bool)
        alive = np.asarray(host["alive_at_done"]).astype(bool)
        t_done = np.asarray(host["t_done"], np.float64)
        # weighted mode trains every selected client (stragglers land
        # late, discounted); drop mode keeps the Eq. 6 survivors.  Either
        # way a client out of coverage at its upload instant is lost.
        train_mask = ((mask > 0) if self.weighted else survivors) & alive
        if not train_mask.any():
            return
        land = np.maximum(np.ceil(t_done / self.cadence).astype(np.int64),
                          1)
        keys = sim._round_keys(rnd)
        lam = self.run_cfg.staleness_lambda
        if sim.client_mesh is not None:
            # sharded: one psum'd partial aggregate per landing tick —
            # the per-tick staleness factor folds into the cohort
            # weights at the trainer (weight_scale), the anchor mass is
            # tracked host-side from the same |D_i| the weights use
            for k in np.unique(land[train_mask]):
                bucket = train_mask & (land == k)
                delay = max(0, self._tick_round(int(k)) - rnd)
                s = (staleness_weight(lam, delay) if self.weighted
                     else 1.0)
                trained = pipeline.train_groups_sharded(
                    sim.params, sim.groups, sim._group_steps, bucket,
                    keys, sim.client_mesh, epochs=cfg.local_epochs,
                    batch_size=cfg.batch_size, lr=cfg.lr,
                    prox_mu=cfg.prox_mu, weight_scale=float(s),
                    counters=sim.counters)
                if trained is None:
                    continue
                num, den = trained
                w_data = float(sim.n_valid[bucket].sum())
                self._pending.setdefault(int(k), []).append({
                    "src": rnd, "num": num, "den": den,
                    "anchor": float(w_data * (1.0 - s)),
                    "n": int(bucket.sum()), "delay": delay,
                    "scale": float(s)})
            return
        entries = pipeline.train_groups(
            sim.params, sim.groups, sim._group_steps, train_mask, keys,
            epochs=cfg.local_epochs, batch_size=cfg.batch_size,
            lr=cfg.lr, prox_mu=cfg.prox_mu, return_entries=True,
            counters=sim.counters)
        if entries is None:
            return
        merged, w, row_ids = entries
        land_rows = land[row_ids]            # padding rows keep weight 0
        for k in np.unique(land_rows[w > 0]):
            delay = max(0, self._tick_round(int(k)) - rnd)
            s = staleness_weight(lam, delay) if self.weighted else 1.0
            wk = np.where(land_rows == k, w, 0.0).astype(np.float32)
            live = float(wk.sum())
            self._pending.setdefault(int(k), []).append({
                "src": rnd, "merged": merged,
                "w": (wk * np.float32(s) if s != 1.0 else wk),
                "anchor": float(live * (1.0 - s)),
                "n": int((wk > 0).sum()), "delay": delay,
                "scale": float(s)})

    def _process_due_ticks(self, rnd: int) -> None:
        """Fire every aggregation tick due by the end of round ``rnd``
        (in tick order: each tick is its own FedAvg event over the
        updates landing there).  An empty or zero-weight tick leaves the
        global model untouched — the streaming no-op broadcast."""
        sim = self.sim
        stats = self._stats[rnd]
        for k in self._due_ticks(rnd):
            items = self._pending.pop(k)
            anchor = sum(it["anchor"] for it in items)
            if sim.client_mesh is not None:
                num = items[0]["num"]
                den = items[0]["den"]
                for it in items[1:]:
                    num = jax.tree.map(jnp.add, num, it["num"])
                    den = den + it["den"]
                if anchor > 0.0:             # staleness-discounted mass
                    a = jnp.float32(anchor)
                    num = jax.tree.map(
                        lambda nl, p: nl + a * p.astype(nl.dtype),
                        num, sim.params)
                    den = den + a
                # the summed partials are fresh/single-use: the donated
                # finisher is safe here
                sim.params = pipeline.aggregate_sharded(sim.params,
                                                        (num, den))
            else:
                w = np.concatenate([it["w"] for it in items])
                if float(w.sum()) + anchor <= 0.0:
                    continue                 # zero-weight tick: no-op
                merged = items[0]["merged"] if len(items) == 1 else \
                    jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                                 *[it["merged"] for it in items])
                if anchor > 0.0:
                    merged = jax.tree.map(
                        lambda m, p: jnp.concatenate([m, p[None]]),
                        merged, sim.params)
                    w = np.append(w, np.float32(anchor))
                sim.params = _fedavg_pool(merged, jnp.asarray(w))
            for it in items:
                stats["n_agg"] += it["n"]
                if it["delay"] >= 1:
                    stats["n_stale"] += it["n"]
                stats["eff"] += it["n"] * it["scale"]
                stats["hist"][min(it["delay"], _HIST_BINS - 1)] += it["n"]

    # -- preemption safety (ISSUE 10) ----------------------------------
    def capture_state(self) -> Dict:
        """The wrapped simulation's state plus the streaming server's
        own: the pending landing-tick pools (device pytrees pulled to
        host) and the open per-round stat accumulators.  Together these
        make a mid-stream kill invisible — stragglers enqueued rounds
        ago land at the same tick with the same weights after resume."""
        pending = {}
        for k, items in self._pending.items():
            out = []
            for it in items:
                e: Dict = {}
                for name, v in it.items():
                    if name in ("merged", "num", "den"):
                        e[name] = jax.device_get(v)
                    elif name == "w":
                        e[name] = np.asarray(v, np.float32)
                    else:
                        e[name] = _ENTRY_SCALARS[name](v)
                out.append(e)
            pending[str(k)] = out
        stats = {str(r): {"n_agg": int(s["n_agg"]),
                          "n_stale": int(s["n_stale"]),
                          "eff": float(s["eff"]),
                          "hist": [int(h) for h in s["hist"]]}
                 for r, s in self._stats.items()}
        return {"sim": self.sim.capture_state(),
                "pending": pending, "stats": stats}

    def restore_state(self, state: Dict,
                      extra: Optional[Dict] = None) -> None:
        self.sim.restore_state(state["sim"], extra)
        self._pending = {}
        for k, items in state["pending"].items():
            out = []
            for it in items:
                e = {}
                for name, v in it.items():
                    if name in ("merged", "num", "den"):
                        e[name] = jax.tree.map(jnp.asarray, v)
                    elif name == "w":
                        e[name] = np.asarray(v, np.float32)
                    else:
                        e[name] = _ENTRY_SCALARS[name](v)
                out.append(e)
            self._pending[int(k)] = out
        self._stats = {int(r): {"n_agg": int(s["n_agg"]),
                                "n_stale": int(s["n_stale"]),
                                "eff": float(s["eff"]),
                                "hist": [int(h) for h in s["hist"]]}
                       for r, s in state["stats"].items()}

    # -- metrics rows ---------------------------------------------------
    def _round_row(self, rnd: int, host: Dict, acc_count: jax.Array,
                   n_test: int) -> Dict[str, float]:
        row = self.sim._round_row(rnd, host, acc_count, n_test)
        if self.sync_equivalent:
            return row
        st = self._stats.pop(rnd)
        row["n_aggregated"] = st["n_agg"]
        row["stale_frac"] = (st["n_stale"] / st["n_agg"]
                             if st["n_agg"] else 0.0)
        row["n_effective"] = st["eff"]
        row["rounds_behind_hist"] = "/".join(str(h) for h in st["hist"])
        return row

    def finish_round(self, rnd: int,
                     state: Dict[str, jax.Array]) -> Dict[str, float]:
        """Complete round ``rnd`` from a selection-prefix output (the
        sweep harness's per-seed entry point)."""
        host = self.sim.gather_selection(rnd, state)
        self._dispatch_training(rnd, host)
        with trace.span(trace.DISPATCH, round=rnd):
            acc, n_test = evaluate_accuracy_async(
                self.sim.params, self.sim.test_images,
                self.sim.test_labels, batch=256)
        return self._round_row(rnd, host, acc, n_test)

    # -- drivers ---------------------------------------------------------
    def run(self, n_rounds: Optional[int] = None,
            overlap: Optional[bool] = None, *,
            checkpointer=None,
            resume: Optional[bool] = None) -> List[Dict[str, float]]:
        """Drive ``n`` rounds.  Identical schedule to the sync drivers —
        serial or round-ahead — with the tick pool swapped in behind
        ``_dispatch_training``, so the prefix executables and dispatch
        order match the barrier drivers call for call.  Checkpoint /
        resume mirrors ``FLSimulation.run`` with the pending-tick queue
        riding along in every snapshot."""
        sim = self.sim
        n = n_rounds or sim.cfg.n_rounds
        ckpt = build_round_checkpointer(self.run_cfg, checkpointer)
        resume = self.run_cfg.resume if resume is None else resume
        rows, start = resume_rows(self, ckpt, resume)
        if overlap is None:
            overlap = self.run_cfg.overlap_rounds
        if not overlap:
            for r in range(start, n):
                with trace.round_span(r):
                    rows.append(self.finish_round(r,
                                                  sim.selection_state(r)))
                    checkpoint_round(self, ckpt, r, rows)
            return rows
        if start >= n:
            return rows
        state = sim.selection_state(start)
        for r in range(start, n):
            with trace.round_span(r):
                host = sim.gather_selection(r, state)
                self._dispatch_training(r, host)
                ahead = r + 1 < n            # round-ahead: r+1's prefix
                with trace.span(trace.DISPATCH, round=r,
                                prefix_round=r + 1 if ahead else None):
                    acc, n_test = evaluate_accuracy_async(
                        sim.params, sim.test_images, sim.test_labels,
                        batch=256)
                    if ahead:
                        state = sim.selection_state(r + 1)
                rows.append(self._round_row(r, host, acc, n_test))
                checkpoint_round(self, ckpt, r, rows)
        return rows
