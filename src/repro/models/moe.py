"""Mixture-of-Experts layer: top-k routing, capacity dispatch, aux losses.

Dispatch uses an argsort-based position-in-expert computation (O(T·k)
memory — no (T, E, C) one-hot tensor) followed by scatter into a per-expert
(E, C, D) buffer.  Under expert-parallel sharding (experts over the
``model`` mesh axis) the scatter/gather lower to all-to-all collectives,
which is exactly what the roofline's collective term should see.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import Params, dense_init, PARAM_DTYPE
from repro.sharding.api import constrain


def init_moe(key: jax.Array, cfg, d: int) -> Params:
    e = cfg.num_experts
    ff = cfg.moe_d_ff or cfg.d_ff
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], d, e, scale=0.02),
        "wi": jax.vmap(lambda k: dense_init(k, d, ff))(
            jax.random.split(ks[1], e)),
        "wg": jax.vmap(lambda k: dense_init(k, d, ff))(
            jax.random.split(ks[2], e)),
        "wo": jax.vmap(lambda k: dense_init(k, ff, d))(
            jax.random.split(ks[3], e)),
    }


def _positions_in_expert(flat_e: jax.Array, num_experts: int) -> jax.Array:
    """Rank of each assignment within its expert (stable order)."""
    tk = flat_e.shape[0]
    perm = jnp.argsort(flat_e, stable=True)
    counts = jnp.zeros((num_experts,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts                  # exclusive cumsum
    pos_sorted = jnp.arange(tk, dtype=jnp.int32) - starts[flat_e[perm]]
    return jnp.zeros((tk,), jnp.int32).at[perm].set(pos_sorted)


def moe_capacity(cfg, tokens: int) -> int:
    cap = int(cfg.capacity_factor * cfg.experts_per_token * tokens
              / cfg.num_experts)
    return max(8, -(-cap // 8) * 8)                       # round up to 8


def apply_moe(cfg, p: Params, x: jax.Array) -> Tuple[jax.Array, Dict[str, Any]]:
    """x: (B, S, D) -> (B, S, D), aux {lb_loss, z_loss, expert_load}.

    Dispatches to the shard_map expert-parallel path when a production
    mesh is active (see ``_apply_moe_ep``); falls back to the dense
    jit-level dispatch otherwise (CPU tests, debug meshes).
    """
    from repro.sharding.api import current_mesh
    mesh = current_mesh()
    if mesh is not None and "model" in mesh.shape:
        msz = mesh.shape["model"]
        bsz = 1
        for a in ("pod", "data"):
            if a in mesh.shape:
                bsz *= mesh.shape[a]
        # EP pays a per-layer psum + weight gather: only worth it when the
        # token volume dwarfs the expert count (train/prefill, not decode)
        tokens = x.shape[0] * x.shape[1]
        if (cfg.num_experts % msz == 0 and x.shape[0] % bsz == 0
                and msz > 1 and tokens > 8 * cfg.num_experts):
            return _apply_moe_ep(cfg, p, x, mesh)
    return _apply_moe_dense(cfg, p, x)


def _apply_moe_dense(cfg, p: Params, x: jax.Array
                     ) -> Tuple[jax.Array, Dict[str, Any]]:
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.experts_per_token
    cap = moe_capacity(cfg, t)
    xt = x.reshape(t, d)
    dt = x.dtype

    logits = (xt @ p["router"].astype(dt)).astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, sel = jax.lax.top_k(probs, k)                             # (T, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)

    flat_e = sel.reshape(-1)                                     # (T*k,)
    pos = _positions_in_expert(flat_e, e)
    keep = (pos < cap).astype(dt)
    pos_c = jnp.minimum(pos, cap - 1)
    tok = jnp.arange(t * k, dtype=jnp.int32) // k

    # dispatch: (E, C, D)
    buf = jnp.zeros((e, cap, d), dt).at[flat_e, pos_c].add(
        xt[tok] * keep[:, None])

    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(dt))
    h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(dt))
    y_e = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(dt))

    # combine
    gathered = y_e[flat_e, pos_c] * keep[:, None] * w.reshape(-1)[:, None].astype(dt)
    y = jnp.zeros((t, d), dt).at[tok].add(gathered)

    # aux losses (Switch-style load balance + router z-loss)
    me = probs.mean(0)                                           # (E,)
    assign = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0) / (t * k)
    lb = e * jnp.sum(me * assign)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = {"lb_loss": lb, "z_loss": z, "expert_load": assign}
    return y.reshape(b, s, d), aux


# --------------------------------------------------------------------------
# shard_map expert parallelism
# --------------------------------------------------------------------------
#
# Tokens are sharded over ('pod','data') and *replicated over 'model'*
# (the residual stream is model-replicated), so every model shard can
# route the full local token block and process only its own E/m experts:
# no all-to-all is needed for dispatch, and the combine is one psum over
# 'model' of the (T_local, D) partial outputs.  Expert weights are stored
# ZeRO-style as (E->'model', dim1->'data') and all-gathered over 'data'
# at use (in bf16).  Capacity is computed from *local* tokens, which keeps
# the dispatch buffer device-sized — the flaw of the jit-level dense path
# at production scale (a global-capacity (E, C, D) buffer that GSPMD
# cannot shard through the scatter).

def _apply_moe_ep(cfg, p: Params, x: jax.Array, mesh
                  ) -> Tuple[jax.Array, Dict[str, Any]]:
    from jax.sharding import PartitionSpec as P

    e, k = cfg.num_experts, cfg.experts_per_token
    msz = mesh.shape["model"]
    e_loc = e // msz
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    data_ax = "data" if "data" in mesh.shape else None
    dt = x.dtype

    def body(x_blk, router, wi, wg, wo):
        bl, s, d = x_blk.shape
        xt = x_blk.reshape(-1, d)
        tl = xt.shape[0]
        logits = (xt @ router.astype(dt)).astype(jnp.float32)   # (Tl, E)
        probs = jax.nn.softmax(logits, axis=-1)
        w, sel = jax.lax.top_k(probs, k)
        w = (w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)).astype(dt)

        flat_e = sel.reshape(-1)
        pos = _positions_in_expert(flat_e, e).reshape(tl, k)
        cap = moe_capacity(cfg, tl)

        m_idx = jax.lax.axis_index("model")
        # per-routing-slot scatters: transients stay (T_local, D), not
        # (T_local*k, D)
        buf = jnp.zeros((e_loc, cap, d), dt)
        slot = []
        for j in range(k):
            ej, pj = sel[:, j], pos[:, j]
            mine = (pj < cap) & (ej >= m_idx * e_loc) \
                & (ej < (m_idx + 1) * e_loc)
            le = jnp.clip(ej - m_idx * e_loc, 0, e_loc - 1)
            pc = jnp.minimum(pj, cap - 1)
            buf = buf.at[le, pc].add(xt * mine.astype(dt)[:, None])
            slot.append((le, pc, mine))

        def full(wt):
            if data_ax is None:
                return wt.astype(dt)
            return jax.lax.all_gather(wt.astype(dt), data_ax, axis=1,
                                      tiled=True)

        h = jnp.einsum("ecd,edf->ecf", buf, full(wi))
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", buf, full(wg))
        y_e = jnp.einsum("ecf,efd->ecd", h, full(wo))

        y = jnp.zeros((tl, d), dt)
        for j, (le, pc, mine) in enumerate(slot):
            y = y + y_e[le, pc] * mine.astype(dt)[:, None] * w[:, j, None]
        y = jax.lax.psum(y, "model")

        me = probs.mean(0)
        assign = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0) / (tl * k)
        lb = e * jnp.sum(me * assign)
        z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        if batch_axes:
            lb = jax.lax.pmean(lb, batch_axes)
            z = jax.lax.pmean(z, batch_axes)
            assign = jax.lax.pmean(assign, batch_axes)
        return y.reshape(bl, s, d), lb, z, assign

    xspec = P(batch_axes if batch_axes else None, None, None)
    wspec = P("model", "data" if data_ax else None, None)
    y, lb, z, assign = jax.shard_map(
        body, mesh=mesh,
        in_specs=(xspec, P(None, None), wspec, wspec, wspec),
        out_specs=(xspec, P(), P(), P()),
    )(x, p["router"], p["wi"], p["wg"], p["wo"])
    return y, {"lb_loss": lb, "z_loss": z, "expert_load": assign}
