"""A whole run with the timed path broken underneath must report
``correct: false``, once for each fault a cell of this system can
have.  (The exchange between chips has no fault here: every cell runs
on one chip.)"""
import jax
import jax.numpy as jnp
import pytest

from bench.tests.conftest import run_cell

CELLS = ["tiny-suite.batch", "tiny-mixed.open"]


def _unchanged_state(monkeypatch):
    """The SM step returns its state unchanged: every block leaves its
    memory as it found it and counts nothing."""
    import importlib
    ex = importlib.import_module("repro.runtime.executor")
    from repro.core.pipeline import Counters

    def body(cfg, n_warps, code, block_dim, block_dim_xy, block_xy,
             grid_xy, gmem):
        z = jnp.zeros((), jnp.int32)
        n_op = 28
        return gmem, jnp.zeros(gmem.shape, bool), Counters(
            jnp.zeros((n_op,), jnp.int32), jnp.zeros((n_op,), jnp.int32),
            z, z, z, z)
    monkeypatch.setattr(ex, "run_block_body", body)


def _half_the_blocks(monkeypatch):
    """Half of every launch's blocks are left out of the schedule."""
    import importlib
    ex = importlib.import_module("repro.runtime.executor")
    orig = ex._block_positions
    monkeypatch.setattr(ex, "_block_positions",
                        lambda grid: orig(grid)[:max(1, len(orig(grid)) // 2)])


def _answer_altered(monkeypatch):
    """One word of every result is altered where results are made."""
    import importlib
    ex = importlib.import_module("repro.runtime.executor")
    orig = ex.DeviceGrid.to_results

    def to_results(self, host_gmem=True):
        return [r._replace(gmem=jnp.asarray(r.gmem).at[-1].add(1))
                for r in orig(self, host_gmem)]
    monkeypatch.setattr(ex.DeviceGrid, "to_results", to_results)


def _counter_altered(monkeypatch):
    """One cycle count of every result is altered where it is made."""
    import importlib
    import numpy as np
    ex = importlib.import_module("repro.runtime.executor")
    orig = ex.DeviceGrid.to_results

    def to_results(self, host_gmem=True):
        out = []
        for r in orig(self, host_gmem):
            c = np.array(r.cycles_per_block)
            c[0] += 1
            out.append(r._replace(cycles_per_block=c))
        return out
    monkeypatch.setattr(ex.DeviceGrid, "to_results", to_results)


FAULTS = {"unchanged_state": _unchanged_state,
          "half_the_blocks": _half_the_blocks,
          "answer_altered": _answer_altered,
          "counter_altered": _counter_altered}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(tiny_root, monkeypatch, cell, fault):
    jax.clear_caches()
    FAULTS[fault](monkeypatch)
    try:
        rc, line, err = run_cell(tiny_root, cell, seed=2**31 + 11)
    finally:
        jax.clear_caches()
    assert rc == 0, err
    assert line["correct"] is False, err
