"""The served path's kernels and steps, compiled for a described TPU v5e.

Nothing runs: each test lowers and compiles for a ``v5e:2x2`` topology
that is described, not attached, which is where the TPU's compiler
refuses what interpret mode accepts (unaligned slices, too much fast
memory, gathers Mosaic cannot lower).  The topology is described inside
a module-scoped fixture, never while a module is imported, and the
persistent compilation cache is off around these compiles: an
executable built for a described chip cannot be read back here.

A described topology still reports ``jax.default_backend() == "cpu"``,
so the kernels are called with ``interpret=False`` directly, and the
pipeline's platform decision is steered from the test.
"""
import importlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import isa
from repro.core.pipeline import MachineConfig, init_state
from repro.core.pipeline.fused import fused_sm_step
from repro.kernels.simt_alu import simt_alu
from repro.launch import mesh as M
from repro.runtime import executor as ex

#: matmul at n = 256, the largest footprint of the paper suite: a
#: 196608-word gmem in the 262144-word bucket, 8 warps, the 96-row code
#: bucket, one launch, a 2-SM dispatch group
G_WORDS, CODE_ROWS, N_WARPS, N_SM = 262144, 96, 8, 2


@pytest.fixture(scope="module")
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # a CPU trace of the same step (interpret-mode kernels) must not be
    # reused for the chip
    jax.clear_caches()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_cache):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _positions_args(sh_launch, sh_pos, n_sm):
    """Shapes of one ``_run_positions`` dispatch group: one super-step,
    so one block per SM."""
    width = n_sm
    return (_shape((1, CODE_ROWS, isa.NUM_FIELDS), sh_launch),
            _shape((1,), sh_launch), _shape((1, 2), sh_launch),
            _shape((1, 2), sh_launch),
            _shape((width,), sh_pos), _shape((width, 2), sh_pos),
            _shape((width,), sh_pos, jnp.bool_), _shape((width,), sh_pos),
            _shape((1, G_WORDS), sh_launch), _shape((2, n_sm), sh_launch))


@pytest.mark.parametrize("n_warps", [1, 2, 4, 8])
def test_simt_alu_compiles(one_chip, n_warps):
    """The execute-stage kernel at every warp count the suite uses."""
    lanes = _shape((n_warps, isa.WARP_SIZE), one_chip)
    compiled = jax.jit(lambda *a: simt_alu(*a, interpret=False)).lower(
        _shape((n_warps,), one_chip), *[lanes] * 6).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_run_positions_compiles(one_chip, backend, monkeypatch):
    """One dispatch group of the executor at the largest paper footprint;
    with ``"pallas"`` the execute stage is the Mosaic kernel."""
    monkeypatch.setattr(importlib.import_module(
        "repro.core.pipeline.execute"), "interpret_mode", lambda: False)
    compiled = ex._run_positions.lower(
        MachineConfig(execute_backend=backend), N_WARPS,
        *_positions_args(one_chip, one_chip, N_SM)).compile()
    mem = compiled.memory_analysis()
    print(mem)
    assert mem.temp_size_in_bytes < 64 << 20
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "pallas")


def test_run_positions_register_files_not_scattered(one_chip, monkeypatch):
    """The step writes the register and predicate files by a one-hot
    select: the compiled program holds no scatter whose result has the
    shape of either file, while the memory stores stay scatters."""
    monkeypatch.setattr(importlib.import_module(
        "repro.core.pipeline.execute"), "interpret_mode", lambda: False)
    cfg = MachineConfig()
    compiled = ex._run_positions.lower(
        cfg, N_WARPS, *_positions_args(one_chip, one_chip, N_SM)).compile()
    scattered = set(re.findall(r"= (\w+\[[\d,]*\])\S* scatter\(",
                               compiled.as_text()))
    lanes = (N_SM, N_WARPS, isa.WARP_SIZE)
    regs = "s32[%s]" % ",".join(map(str, lanes + (cfg.n_regs,)))
    pred = "s32[%s]" % ",".join(map(str, lanes + (4,)))
    assert f"s32[{N_SM},{G_WORDS + 1}]" in scattered   # global stores
    assert regs not in scattered and pred not in scattered, scattered


#: CSR SpMV at n = 8,192, the largest tenant memory a cell serves: a
#: 2,097,152-word bucket, 4 warps, the 64-row code bucket
SPMV_G_WORDS, SPMV_CODE_ROWS, SPMV_N_WARPS = 2097152, 64, 4


def test_run_positions_memory_row_not_copied(one_chip, monkeypatch):
    """A dispatch group's blocks run in one machine loop that takes the
    memories from the step unselected: at the largest memory bucket the
    compiled program holds no copy and no select of the group's memory
    row or written mask, and the global and written-mask stores stay
    scatters of that shape (in place)."""
    monkeypatch.setattr(importlib.import_module(
        "repro.core.pipeline.execute"), "interpret_mode", lambda: False)
    args = list(_positions_args(one_chip, one_chip, N_SM))
    args[0] = _shape((1, SPMV_CODE_ROWS, isa.NUM_FIELDS), one_chip)
    args[8] = _shape((1, SPMV_G_WORDS), one_chip)
    text = ex._run_positions.lower(MachineConfig(), SPMV_N_WARPS,
                                   *args).compile().as_text()
    ops = set(re.findall(r"= (\w+\[[\d,]*\])\S* (copy|select|scatter)\(",
                         text))
    for row in (f"s32[{N_SM},{SPMV_G_WORDS + 1}]",
                f"pred[{N_SM},{SPMV_G_WORDS + 1}]"):
        assert (row, "scatter") in ops, row
        assert (row, "copy") not in ops and (row, "select") not in ops, row


def test_sharded_run_positions_compiles(topo):
    """The ``shard_sm`` dispatch over a mesh of the four described chips:
    one SM per chip, gmem merged across chips by collectives."""
    mesh = M.make_mesh((4,), ("sm",), devices=topo.devices)
    run = ex._sharded_run_positions(MachineConfig(), N_WARPS, mesh, 4, 1)
    args = _positions_args(NamedSharding(mesh, P()),
                           NamedSharding(mesh, P("sm")), 4)
    compiled = run.lower(*args).compile()
    assert "all-reduce" in compiled.as_text()


def test_fused_sm_step_refusal_propagates(one_chip):
    """Mosaic refuses the fused step's fetch gather over a whole-array
    ref; the refusal reaches the caller instead of falling back to the
    interpreter or to the staged step."""
    cfg = MachineConfig(execute_backend="pallas_fused")
    st = jax.eval_shape(lambda g: init_state(cfg, N_WARPS, 256, g),
                        jax.ShapeDtypeStruct((G_WORDS,), jnp.int32))
    st = jax.tree.map(lambda a: _shape(a.shape, one_chip, a.dtype), st)
    lut = jnp.asarray(isa.COND_LUT)

    def step(code, lut, bd, bxy, gxy, st):
        return fused_sm_step(cfg, code, lut, bd, bxy, gxy, st,
                             interpret=False)

    with pytest.raises(ValueError, match="Shape mismatch"):
        jax.jit(step).lower(
            _shape((CODE_ROWS, isa.NUM_FIELDS), one_chip),
            _shape(lut.shape, one_chip, lut.dtype),
            *[_shape((2,), one_chip)] * 3, st).compile()
