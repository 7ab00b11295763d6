"""Selection protocols as mesh collectives (shard_map).

This is the TPU-native restatement of the paper's communication claim.
Participants are sharded over the ``data`` axis as contiguous road
segments.  Three protocols, in decreasing communication cost:

- ``ccs_state_gather``   — classical CFL: the *full state vector* of every
  participant is gathered to the (replicated) server: one all-gather of
  (N, state_dim) floats.
- ``ccs_fuzzy_gather``   — CFL-fuzzy [16]: evaluation happens locally, so
  only the scalar evaluation is gathered: one all-gather of (N,) floats.
- ``dcs_neighbor_exchange`` — the paper's scheme: each shard exchanges its
  boundary window with its two road-adjacent shards only (two
  collective-permutes of (W,) floats), then elects locally.  Communication
  is O(W) per device, *independent of N* — the Eq. 5 elimination.

``benchmarks/bench_selection_collectives.py`` lowers all three and counts
collective bytes in the compiled HLO.
"""
from __future__ import annotations

import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.fuzzy import FuzzyEvaluator


def _shmap(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)


def _elect_block(pos_i, ev_i, idx_i, pos_all, ev_all, idx_all, *,
                 comm_range: float, top_m: int, e_tau: float):
    """Election for a block of vehicles against a candidate window."""
    d = jnp.abs(pos_i[:, None] - pos_all[None, :])
    valid = (d <= comm_range) & (ev_all[None, :] >= e_tau)
    better = (ev_all[None, :] > ev_i[:, None]) | (
        (ev_all[None, :] == ev_i[:, None]) & (idx_all[None, :] < idx_i[:, None]))
    n_better = (valid & better).sum(axis=1)
    return ((ev_i >= e_tau) & (n_better < top_m)).astype(jnp.int32)


# --------------------------------------------------------------------------

def make_ccs_state_gather(mesh: Mesh, evaluator: FuzzyEvaluator,
                          n_clients: int, state_dim: int,
                          axis: str = "data") -> Callable:
    """states (N, state_dim) sharded -> selection mask (N,) sharded.

    The server (replicated computation) receives every participant's raw
    state, evaluates, sorts, selects — the CFL scheme of Fig. 1a.
    """
    def body(states):
        full = jax.lax.all_gather(states, axis, axis=0, tiled=True)
        feats = full[:, :4]                      # SQ, TA, CC, LF
        evals = evaluator.evaluate(feats)
        n = evals.shape[0]
        _, top = jax.lax.top_k(evals, n_clients)
        mask = jnp.zeros((n,), jnp.int32).at[top].set(1)
        i = jax.lax.axis_index(axis)
        blk = states.shape[0]
        return jax.lax.dynamic_slice_in_dim(mask, i * blk, blk)

    return _shmap(body, mesh, in_specs=P(axis), out_specs=P(axis))


def make_ccs_fuzzy_gather(mesh: Mesh, n_clients: int,
                          axis: str = "data") -> Callable:
    """evals (N,) sharded (computed locally) -> mask (N,) sharded.
    Only the scalar evaluations travel — Fig. 1b."""
    def body(evals):
        full = jax.lax.all_gather(evals, axis, axis=0, tiled=True)
        n = full.shape[0]
        _, top = jax.lax.top_k(full, n_clients)
        mask = jnp.zeros((n,), jnp.int32).at[top].set(1)
        i = jax.lax.axis_index(axis)
        blk = evals.shape[0]
        return jax.lax.dynamic_slice_in_dim(mask, i * blk, blk)

    return _shmap(body, mesh, in_specs=P(axis), out_specs=P(axis))


def make_dcs_neighbor_exchange(mesh: Mesh, *, comm_range: float = 200.0,
                               top_m: int = 2, e_tau: float = 30.0,
                               window: int = 0,
                               axis: str = "data") -> Callable:
    """(pos (N,), evals (N,)) sharded -> mask (N,) sharded.

    Each shard sends only its boundary ``window`` (defaults to the whole
    shard block) to the left and right road-adjacent shards via
    collective_permute — communication O(window), independent of N.
    """
    n_shards = mesh.shape[axis]

    def body(pos, evals):
        blk = pos.shape[0]
        w = window or blk
        base = jax.lax.axis_index(axis) * blk
        idx = base + jnp.arange(blk, dtype=jnp.int32)

        if n_shards == 1:                      # degenerate: no neighbours
            return _elect_block(pos, evals, idx, pos, evals, idx,
                                comm_range=comm_range, top_m=top_m,
                                e_tau=e_tau)

        right_perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
        left_perm = [(i, (i - 1) % n_shards) for i in range(n_shards)]

        def send(x_slice, perm):
            return jax.lax.ppermute(x_slice, axis, perm)

        # my right edge -> right neighbour's left window, and vice versa
        from_left = tuple(send(z[-w:], right_perm)
                          for z in (pos, evals, idx.astype(jnp.float32)))
        from_right = tuple(send(z[:w], left_perm)
                           for z in (pos, evals, idx.astype(jnp.float32)))

        cand_pos = jnp.concatenate([from_left[0], pos, from_right[0]])
        cand_ev = jnp.concatenate([from_left[1], evals, from_right[1]])
        cand_idx = jnp.concatenate([from_left[2], idx.astype(jnp.float32),
                                    from_right[2]]).astype(jnp.int32)
        return _elect_block(pos, evals, idx, cand_pos, cand_ev, cand_idx,
                            comm_range=comm_range, top_m=top_m, e_tau=e_tau)

    return _shmap(body, mesh, in_specs=(P(axis), P(axis)),
                  out_specs=P(axis))
