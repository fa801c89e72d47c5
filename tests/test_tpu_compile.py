"""The served path's SM step, compiled for a described TPU v5e.

Nothing runs: each test lowers and compiles for a ``v5e:2x2`` topology
that is described, not attached, which is where the TPU's compiler
refuses what the CPU accepts (too much fast memory, layouts it cannot
tile).  The topology is described inside a module-scoped fixture, never
while a module is imported, and the persistent compilation cache is off
around these compiles: an executable built for a described chip cannot
be read back here.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import isa
from repro.core.pipeline import MachineConfig
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
    # a CPU trace of the same step must not be reused for the chip
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
def test_run_positions_compiles(one_chip, n_warps):
    """One dispatch group of the executor at the largest paper footprint,
    at every warp count the cells run: plain XLA ops, no custom call,
    within the fast-memory budget."""
    compiled = ex._run_positions.lower(
        MachineConfig(), n_warps,
        *_positions_args(one_chip, one_chip, N_SM)).compile()
    mem = compiled.memory_analysis()
    print(mem)
    assert mem.temp_size_in_bytes < 64 << 20
    assert "tpu_custom_call" not in compiled.as_text()


def test_run_positions_register_files_not_scattered(one_chip):
    """The step writes the register and predicate files by a one-hot
    select: the compiled program holds no scatter whose result has the
    shape of either file, while the memory stores stay scatters."""
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


def _guarded_scatters(text):
    """For each branch of a ``conditional`` and each body of a
    ``while`` in an HLO module's text, the shapes of the scatters it
    reaches (through fusions and nested computations)."""
    calls, scatters, guards, name = {}, {}, [], None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            calls[name], scatters[name] = set(), set()
            continue
        if name is None:
            continue
        callees = re.findall(r"(?:calls|to_apply|condition|"
                             r"true_computation|false_computation)"
                             r"=%([\w.\-]+)", line)
        body = re.search(r"body=%([\w.\-]+)", line)
        branches = re.search(r"branch_computations=\{([^}]*)\}", line)
        here = ([body.group(1)] if body else []) + (
            re.findall(r"%([\w.\-]+)", branches.group(1)) if branches
            else [])
        guards += here
        calls[name].update(callees + here)
        hit = re.search(r"= (\w+\[[\d,]*\])\S* scatter\(", line)
        if hit:
            scatters[name].add(hit.group(1))

    def reach(root):
        seen, todo, shapes = set(), [root], set()
        while todo:
            c = todo.pop()
            if c not in seen:
                seen.add(c)
                shapes |= scatters.get(c, set())
                todo += calls.get(c, ())
        return shapes
    return [reach(g) for g in guards]


def _assert_stores_guarded(text, g_words):
    """Each memory port's scatters run under a guard of their own: a
    branch or loop body that reaches the port's scatters — the global
    row with the written mask, or shared memory — and no other scatter,
    so the step runs them only when the guard holds."""
    guarded = _guarded_scatters(text)
    smem = MachineConfig().smem_words + 1
    for port in ({f"s32[{N_SM},{g_words + 1}]",
                  f"pred[{N_SM},{g_words + 1}]"},
                 {f"s32[{N_SM},{smem}]"}):
        assert port in guarded, (port, [g for g in guarded if g])


def _assert_rows_not_copied(text, g_words):
    """No copy — synchronous or asynchronous, as between on-chip memory
    and HBM around a conditional — and no select of the group's memory
    row, written mask or shared memory."""
    ops = set(re.findall(
        r"= \(?(\w+\[[\d,]*\])[^=]*? (copy|copy-start|select)\(", text))
    for row in (f"s32[{N_SM},{g_words + 1}]", f"pred[{N_SM},{g_words + 1}]",
                f"s32[{N_SM},{MachineConfig().smem_words + 1}]"):
        assert not {op for r, op in ops if r == row}, (row, ops)


def test_run_positions_memory_row_not_copied(one_chip):
    """A dispatch group's blocks run in one machine loop that takes the
    memories from the step unselected: at the largest memory bucket the
    compiled program holds no copy and no select of the group's memory
    row or written mask, and the global and written-mask stores stay
    scatters of that shape (in place), each port's under a guard of its
    own."""
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
    _assert_rows_not_copied(text, SPMV_G_WORDS)
    _assert_stores_guarded(text, SPMV_G_WORDS)


def test_run_positions_stores_guarded(one_chip):
    """The same at the paper suite's largest footprint, 8 warps, where
    every memory row fits in on-chip memory: each port's scatters run
    under a guard of their own, and no memory row is copied or
    selected."""
    text = ex._run_positions.lower(
        MachineConfig(), N_WARPS,
        *_positions_args(one_chip, one_chip, N_SM)).compile().as_text()
    _assert_rows_not_copied(text, G_WORDS)
    _assert_stores_guarded(text, G_WORDS)


def test_sharded_run_positions_compiles(topo):
    """The ``shard_sm`` dispatch over a mesh of the four described chips:
    one SM per chip, gmem merged across chips by collectives."""
    mesh = M.make_mesh((4,), ("sm",), devices=topo.devices)
    run = ex._sharded_run_positions(MachineConfig(), N_WARPS, mesh, 4, 1)
    args = _positions_args(NamedSharding(mesh, P()),
                           NamedSharding(mesh, P("sm")), 4)
    compiled = run.lower(*args).compile()
    assert "all-reduce" in compiled.as_text()
