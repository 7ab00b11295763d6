"""The FL path's Pallas kernels compiled for a TPU v5e at real shapes.

Nothing runs: the TPU compiler installed beside jax compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(unaligned blocks, VMEM overruns, ops Mosaic cannot lower) — which
interpret mode never shows.  The topology is described inside a fixture
only, so importing this file touches no TPU library; where it cannot be
described, the fixture skips.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.mnist_cnn import CONFIG as CNN_CFG
from repro.core.rules import build_rule_table
from repro.fl.partition import PartitionConfig
from repro.fl.rounds import FLSimConfig
from repro.kernels.fuzzy_eval import fuzzy_eval_pallas
from repro.kernels.neighbor_elect import (neighbor_elect_pallas,
                                          windowed_counts_pallas)
from repro.kernels.probe_fuzzy import probe_fuzzy_pallas
from repro.models.cnn import init_cnn


def _table3_probe_rows() -> int:
    """Rows of the Table 3 tight probe pack (12 x 256 + 18 x 45)."""
    part, probe = PartitionConfig(), FLSimConfig().probe_samples
    small = part.n_clients - part.big_clients
    return (part.big_clients * min(part.big_quantity, probe)
            + small * min(part.small_quantity, probe))


# (n_clients, packed probe rows): Table 3, and the N=1024 fleet of
# 12 x 256 + 1012 x 24 samples
PROBE_CASES = {"table3": (30, _table3_probe_rows()),
               "fleet1k": (1024, 12 * 256 + 1012 * 24)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any failure means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on one described chip, with the persistent compile
    cache off: a TPU executable written here could not be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("case", sorted(PROBE_CASES))
def test_probe_fuzzy_compiles(one_chip, case):
    n, rows = PROBE_CASES[case]
    params = jax.eval_shape(lambda: init_cnn(jax.random.PRNGKey(0),
                                             CNN_CFG))
    params = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                          params)
    table, levels = build_rule_table()

    def f(p, im, lb, sg, counts, aux, means, sigmas, centers):
        return probe_fuzzy_pallas(p, im, lb, sg, counts, aux, means, sigmas,
                                  table, levels, centers, n_clients=n,
                                  interpret=False)

    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa
    _assert_kernel(jax.jit(f).lower(
        params, s((rows, 28, 28, 1)), s((rows,), jnp.int32),
        s((rows,), jnp.int32), s((n,), jnp.int32), s((n, 3)), s((4, 3)),
        s((4, 3)), s((9,))).compile())


def test_fuzzy_eval_compiles(one_chip):
    table, levels = build_rule_table()

    def f(x, means, sigmas, centers):
        return fuzzy_eval_pallas(x, means, sigmas, table, levels, centers,
                                 interpret=False, normalize=True)

    s = lambda shape: _spec(one_chip, shape)  # noqa: E731
    _assert_kernel(jax.jit(f).lower(s((1024, 4)), s((4, 3)), s((4, 3)),
                                    s((9,))).compile())


@pytest.mark.parametrize("n", [30, 1024])
def test_neighbor_elect_compiles(one_chip, n):
    """The dense election: Table 3's 30 vehicles and the N=1024 fleet."""
    def f(pos, ev):
        return neighbor_elect_pallas(pos, ev, comm_range=200.0, top_m=3,
                                     e_tau=30.0, interpret=False)

    _assert_kernel(jax.jit(f).lower(_spec(one_chip, (n,)),
                                    _spec(one_chip, (n,))).compile())


def test_windowed_counts_compiles(one_chip):
    """One shard's sorted counting sweep of an N=65,536 fleet over four
    chips."""
    m = 65536 // 4

    def f(sp, se, sg):
        return windowed_counts_pallas(sp, se, sg, comm_range=200.0,
                                      e_tau=30.0, n_valid=m, window=64,
                                      block=128, interpret=False)

    _assert_kernel(jax.jit(f).lower(
        _spec(one_chip, (m,)), _spec(one_chip, (m,)),
        _spec(one_chip, (m,), jnp.int32)).compile())
