"""Compile the query path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers an ``ops`` wrapper on the pallas lane at
the smoke run's shapes (64 queries, d = 32 and 128, the slot count of a
1M-row snapshot with K = 256, G = K·m = 768 rank groups) and compiles
it for one chip of a ``v5e:2x2`` topology, which raises what the chip's
compiler would raise — block layouts it cannot tile, VMEM overruns.
Interpret-mode tests cannot see these.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

B = 64                 # queries per batch
SLOTS = 256 * 9300     # K · n_max of the smoke run's 1M-row snapshot
G = 256 * 3            # K · m rank groups
C = 9                  # Chebyshev coefficients (degree 8)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "can't"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas_lane(monkeypatch):
    """Steer the wrappers onto the compiled pallas lane with static
    tiles, as on a TPU host (this process's backend is the CPU)."""
    monkeypatch.setattr(ops, "kernel_mode", lambda: "pallas")
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text        # the Pallas kernel is there


@pytest.mark.parametrize("nq,d", [(B, 32), (B, 128), (48, 32)])
def test_pdist_compiles(pallas_lane, one_chip, nq, d):
    """Query→slot distances (kNN), and the builder's pivot-column
    launch (48 = 16 clusters × m pivots as queries)."""
    _compile(lambda q, p: ops.pdist(q, p), one_chip, (nq, d), (SLOTS, d))


@pytest.mark.parametrize("d", [32, 128])
def test_range_filter_compiles(pallas_lane, one_chip, d):
    _compile(lambda q, p, r: ops.range_filter(q, p, r), one_chip,
             (B, d), (SLOTS, d), (B,))


@pytest.mark.parametrize("d", [32, 128])
def test_pdist_rankeval_compiles(pallas_lane, one_chip, d):
    _compile(lambda q, pv, c, lo, hi, n, rg: ops.pdist_rankeval(
        q, pv, c, lo, hi, n, rg), one_chip,
        (B, d), (G, d), (G, C), (G,), (G,), (G,), (B,))


@pytest.mark.parametrize("g", [G, 13])
def test_rankeval_compiles(pallas_lane, one_chip, g):
    """The staged plan's (G, 2B) boundary matrix; 13 groups pads."""
    _compile(lambda x, c, lo, hi, n: ops.rankeval(x, c, lo, hi, n),
             one_chip, (g, 2 * B), (g, C), (g,), (g,), (g,))
