"""A dispatch group's blocks run in one machine loop.

* Under ``jax.vmap`` each block of a group ends exactly as its own
  unbatched machine loop leaves it — memory, written mask, every
  counter and the trip slot — on every step backend, when the blocks
  stop at very different trips and when the runaway guard stops one of
  them beside a block that runs to EXIT.
* What that rests on: stepping a block whose warps are all FINISHED,
  with no thread alive or active, leaves its memories bit-identical,
  sentinel words included.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compiler.kernels import spmv_csr
from repro.core import isa
from repro.core.pipeline import (EXECUTE_BACKENDS, FINISHED, TRIP_SLOT,
                                 MachineConfig, init_state, run_block_body,
                                 split_trips, step_fn)

N = 256                        # two blocks of 128 rows, 4 warps each
N_WARPS = spmv_csr.BD // isa.WARP_SIZE


#: long rows, one in each warp of the second block
LONG_ROWS = (130, 162, 194, 226)


def _skewed_gmem(seed=0, long_len=60):
    """A CSR matrix whose first block of rows is nearly empty (one
    nonzero in every eighth row) and whose second holds the rest: in
    each of its warps one row is ``long_len`` long, so it loops ~60
    times longer."""
    rng = np.random.default_rng(seed)
    lens = np.zeros(N, np.int64)
    lens[:spmv_csr.BD:8] = 1
    lens[list(LONG_ROWS)] = long_len
    others = np.setdiff1d(np.arange(spmv_csr.BD, N), LONG_ROWS)
    rest = spmv_csr.nnz_of(N) - lens.sum()
    lens[others] = rest // len(others)
    lens[others[:rest % len(others)]] += 1
    row_ptr = np.concatenate(([0], np.cumsum(lens)))
    cols = np.concatenate([np.sort(rng.choice(N, k, replace=False))
                           for k in lens]).astype(np.int32)
    nnz = spmv_csr.nnz_of(N)
    return spmv_csr.pack(N, row_ptr, cols,
                         rng.integers(-100, 100, nnz, dtype=np.int32),
                         rng.integers(-100, 100, N, dtype=np.int32))


def _group_args():
    """Three positions, each with its own memory: the nearly empty
    block; the long block; the long block cut to its first warp (32
    threads) with its long row twice as long, which runs twice the trips
    on a quarter of the warps, so on fewer cycles."""
    code = jnp.asarray(spmv_csr.build(N), jnp.int32)
    grid, (bdx, bdy) = spmv_csr.launch(N)
    bxs, bdims = [0, 1, 1], [bdx, bdx, isa.WARP_SIZE]
    k = len(bxs)
    return (jnp.broadcast_to(code, (k, *code.shape)),
            jnp.asarray(bdims, jnp.int32),
            jnp.asarray([[d, bdy] for d in bdims], jnp.int32),
            jnp.asarray([[x, 0] for x in bxs], jnp.int32),
            jnp.broadcast_to(jnp.asarray(grid, jnp.int32), (k, 2)),
            jnp.stack([jnp.asarray(_skewed_gmem(s, n))
                       for s, n in enumerate([60, 60, 120])]))


@functools.partial(jax.jit, static_argnums=0)
def _one(cfg, *args):
    return run_block_body(cfg, N_WARPS, *args)


@functools.partial(jax.jit, static_argnums=0)
def _group(cfg, *args):
    return jax.vmap(functools.partial(run_block_body, cfg, N_WARPS))(*args)


def _trips(cfg, ctr):
    return int(split_trips(cfg, N_WARPS, ctr)[1])


def _assert_group_matches_blocks(cfg, args):
    """Every block of the group against its own unbatched loop; returns
    the blocks' written masks and loop counters."""
    mem, wrt, ctr = _group(cfg, *args)
    singles = []
    for b in range(args[0].shape[0]):
        one_mem, one_wrt, one_ctr = _one(cfg, *(a[b] for a in args))
        np.testing.assert_array_equal(mem[b], one_mem)
        np.testing.assert_array_equal(wrt[b], one_wrt)
        for f in one_ctr._fields:      # op_issues holds the trip slot
            np.testing.assert_array_equal(getattr(ctr, f)[b],
                                          getattr(one_ctr, f), err_msg=f)
        singles.append((one_wrt, one_ctr))
    return zip(*singles)


@functools.partial(jax.jit, static_argnums=0)
def _cycles_at_first_store(cfg, code, block_dim, block_dim_xy, block_xy,
                           grid_xy, gmem):
    """A block's cycles when its next step is the first to store."""
    step = functools.partial(step_fn(cfg), cfg, code,
                             jnp.asarray(isa.COND_LUT), block_dim_xy,
                             block_xy, grid_xy)
    st = jax.lax.while_loop(lambda st: ~jnp.any(step(st).gw), step,
                            init_state(cfg, N_WARPS, block_dim, gmem))
    return st.counters.cycles


@pytest.mark.parametrize("backend", EXECUTE_BACKENDS)
def test_group_loop_matches_each_block(backend):
    """Blocks that stop at very different trips; then the runaway guard
    stops the four-warp long block just before its first store, while
    the one-warp block, on fewer cycles, runs on to EXIT."""
    cfg = MachineConfig(execute_backend=backend)
    args = _group_args()
    _, ctrs = _assert_group_matches_blocks(cfg, args)
    short, long_, one_warp = (_trips(cfg, c) for c in ctrs)
    assert min(long_, one_warp) > 5 * short > 0
    assert int(ctrs[1].op_issues[TRIP_SLOT]) > 0

    cut = int(_cycles_at_first_store(cfg, *(a[1] for a in args)))
    assert int(ctrs[2].cycles) < cut
    guarded = MachineConfig(execute_backend=backend, max_cycles=cut)
    wrt, ctrs = _assert_group_matches_blocks(guarded, args)
    # the long block stopped at a step that would store; the others ran
    # to EXIT as before
    assert int(ctrs[1].cycles) == cut and not wrt[1].any()
    assert _trips(guarded, ctrs[1]) < long_
    assert [_trips(guarded, ctrs[i]) for i in (0, 2)] == [short, one_warp]
    if backend != "reference":       # lockstep: the one warp runs on
        assert one_warp > _trips(guarded, ctrs[1])


@pytest.mark.parametrize("backend", EXECUTE_BACKENDS)
def test_parked_step_leaves_memory_unchanged(backend):
    """A step of a block with every warp FINISHED and no thread alive or
    active, its warps at every instruction of the program in turn (the
    stores included), returns gmem, the written mask and smem as they
    came in, sentinel words included."""
    cfg = MachineConfig(execute_backend=backend)
    rng = np.random.default_rng(7)
    code = jnp.asarray(spmv_csr.build(N), jnp.int32)
    gmem = jnp.asarray(_skewed_gmem())
    st = init_state(cfg, N_WARPS, spmv_csr.BD, gmem)
    st = st._replace(
        wstate=jnp.full_like(st.wstate, FINISHED),
        alive=jnp.zeros_like(st.alive), active=jnp.zeros_like(st.active),
        regs=jnp.asarray(rng.integers(0, N, st.regs.shape), jnp.int32),
        gmem=jnp.asarray(rng.integers(-2**31, 2**31, st.gmem.shape),
                         jnp.int32),
        gw=jnp.asarray(rng.integers(0, 2, st.gw.shape), bool),
        smem=jnp.asarray(rng.integers(-2**31, 2**31, st.smem.shape),
                         jnp.int32))
    step = jax.jit(functools.partial(step_fn(cfg), cfg))
    lut = jnp.asarray(isa.COND_LUT)
    geom = (jnp.asarray([spmv_csr.BD, 1], jnp.int32),
            jnp.asarray([1, 0], jnp.int32), jnp.asarray([2, 1], jnp.int32))
    ops = np.asarray(code)[:, isa.F_OP]
    assert isa.STG in ops
    for pc in range(code.shape[0]):
        out = step(code, lut, *geom, st._replace(
            pc=jnp.full_like(st.pc, pc)))
        for f in ("gmem", "gw", "smem"):
            np.testing.assert_array_equal(getattr(out, f), getattr(st, f),
                                          err_msg=f"{f} at pc {pc}")
