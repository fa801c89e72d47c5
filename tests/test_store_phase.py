"""The machine loop's store phase: each memory port's scatter runs only
on a trip where some lane stores to it.

* Under ``jax.vmap`` of ``run_block_body``, with blocks that store on
  different trips, every block's memory, written mask and counters are
  bit-identical to its own unbatched loop and to the ``reference``
  discipline: global stores alone (a skewed SpMV group, one block
  storing while another still loops or is already parked), shared
  stores with barriers (bitonic beside reduction), a trip on which both
  ports store, and a group that never stores at all.
* The loop counts the trips on which each port's scatter ran: the
  group's count equals a recount from a Python step loop, reaches every
  ``device-execute`` span and the server's ``MetricsRegistry``, and
  adds no counter fetch.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro import runtime as rt
from repro.compiler.kernels import spmv_csr
from repro.core import asm, isa
from repro.core.pipeline import (MachineConfig, block_running, init_state,
                                 run_block_body, sm_step, split_trips,
                                 store_trips)
from repro.core.programs import ALL
from repro.runtime.registry import pad_code

from test_group_loop import _group_args

JNP, REF = MachineConfig(), MachineConfig(execute_backend="reference")
LUT = jnp.asarray(isa.COND_LUT)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _one(cfg, n_warps, *args):
    return run_block_body(cfg, n_warps, *args)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _group(cfg, n_warps, *args):
    return jax.vmap(functools.partial(run_block_body, cfg, n_warps))(*args)


_step = jax.jit(functools.partial(sm_step, JNP))
_running = jax.jit(functools.partial(block_running, JNP))


def _store_flags(n_warps, code, bdim, bd_xy, bxy, gxy, gmem):
    """(trips, 2) bool from a Python step loop: whether some lane
    executed an ``STG`` / ``STS`` on each trip, read from the step's
    active-lane counts, not from its store requests."""
    st = init_state(JNP, n_warps, bdim, gmem)
    flags = []
    while bool(_running(st)):
        new = _step(code, LUT, bd_xy, bxy, gxy, st)
        d = np.subtract(new.counters.op_lanes, st.counters.op_lanes)
        flags.append((d[isa.STG] > 0, d[isa.STS] > 0))
        st = new
    return np.asarray(flags, bool).reshape(-1, 2)


def _group_count(flags):
    """Trips on which some block of the group stored, per port."""
    trips = max(len(f) for f in flags)
    hit = np.zeros((trips, 2), bool)
    for f in flags:
        hit[:len(f)] |= f
    return hit


def _program(name, n, seed=0):
    mod = ALL[name]
    grid, bd = mod.launch(n)
    return (mod.build(n), grid, bd,
            mod.make_gmem(np.random.default_rng(seed), n))


def _stack(blocks):
    """Blocks ``(code, grid, (bdx, bdy), gmem, bxy)`` as one group's
    arguments: code padded with EXIT rows and memory with zeros to the
    longest, and the warps of the widest."""
    rows = max(len(b[0]) for b in blocks)
    words = max(len(b[3]) for b in blocks)
    n_warps = max(-(-bd[0] * bd[1] // isa.WARP_SIZE)
                  for _, _, bd, _, _ in blocks)
    cols = zip(*[(pad_code(code, rows), bd[0] * bd[1], bd, bxy, grid,
                  np.pad(np.asarray(gmem, np.int32), (0, words - len(gmem))))
                 for code, grid, bd, gmem, bxy in blocks])
    return n_warps, tuple(jnp.asarray(np.stack(c), jnp.int32) for c in cols)


def _skewed_spmv():
    return _group_args()


def _bitonic_beside_reduction():
    bit, red = _program("bitonic", 64), _program("reduction", 128)
    return _stack([(*bit, (0, 0)), (*red, (0, 0))])[1]


#: one warp each; on trip 1 the first stores to shared memory and the
#: second to global memory, so both scatters run on that trip (the
#: first stores to global memory again on trip 3)
_SHARED = """
    S2R r0, sr8
    STS [r0+0], r0
    LDS r1, [r0+0]
    STG [r0+32], r1
    EXIT
"""
_GLOBAL = """
    S2R r0, sr8
    STG [r0+0], r0
    EXIT
"""
_NONE = """
    S2R r0, sr8
    IADD r1, r0, #1
    EXIT
"""


def _asm_group(*texts):
    return _stack([(asm.assemble(t), (1, 1), (isa.WARP_SIZE, 1),
                    np.zeros(64, np.int32), (0, 0)) for t in texts])[1]


CASES = {
    "stg_skewed_spmv": _skewed_spmv,
    "sts_bar": _bitonic_beside_reduction,
    "both_ports_one_trip": lambda: _asm_group(_SHARED, _GLOBAL),
    "no_stores": lambda: _asm_group(_NONE, _NONE),
}


@pytest.mark.parametrize("case", CASES)
def test_group_store_phase_exact(case):
    args = CASES[case]()
    n_warps = -(-int(args[1].max()) // isa.WARP_SIZE)
    mem, wrt, ctr = _group(JNP, n_warps, *args)
    got, _ = split_trips(JNP, n_warps, ctr)
    flags = []
    for b in range(args[0].shape[0]):
        one = [a[b] for a in args]
        for cfg in (JNP, REF):
            m, w, c = _one(cfg, n_warps, *one)
            tag = cfg.execute_backend
            np.testing.assert_array_equal(mem[b], m, err_msg=tag)
            np.testing.assert_array_equal(wrt[b], w, err_msg=tag)
            want, _ = split_trips(cfg, n_warps, c)
            for f in want._fields:
                np.testing.assert_array_equal(getattr(got, f)[b],
                                              getattr(want, f),
                                              err_msg=f"{tag} {f}")
        flags.append(_store_flags(n_warps, *one))
    hit = _group_count(flags)
    # every block carries its group's store-phase trips
    np.testing.assert_array_equal(np.asarray(store_trips(ctr)),
                                  np.broadcast_to(hit.sum(0), (len(flags), 2)))
    assert len(hit) == int(split_trips(JNP, n_warps, ctr)[1].max())

    if case == "stg_skewed_spmv":
        assert not hit[:, 1].any() and hit[:, 0].any()
        ends = [len(f) for f in flags]
        # a block stores while another loops on without storing, and
        # after another has stopped and is stepped parked
        assert any(f[t, 0] and any(len(g) > t and not g[t, 0]
                                   for g in flags if g is not f)
                   for f in flags for t in range(len(f)))
        assert any(f[t, 0] and min(ends) <= t
                   for f in flags for t in range(len(f)))
    elif case == "sts_bar":
        assert hit[:, 1].any()
        first, second = (set(np.flatnonzero(f[:, 1])) for f in flags)
        assert first and second and first != second
    elif case == "both_ports_one_trip":
        assert hit[:, 0].sum() == 2 and hit[:, 1].sum() == 1 and hit[1].all()
        np.testing.assert_array_equal(mem[0, 32:64], np.arange(32))
        np.testing.assert_array_equal(mem[1, :32], np.arange(32))
    else:
        assert not hit.any() and not np.asarray(wrt).any()


def _expected_groups(code, grid, bd, gmem):
    """Per dispatch group of width 2 (blocks in launch order, a lone
    last block beside a padded duplicate of the launch's first):
    ``(trips, gstore_trips, sstore_trips)`` from a Python step loop."""
    bdx, bdy = bd if isinstance(bd, tuple) else (bd, 1)
    n_warps = -(-bdx * bdy // isa.WARP_SIZE)
    pos = [(x, y) for y in range(grid[1]) for x in range(grid[0])]
    common = (jnp.asarray(code, jnp.int32), jnp.int32(bdx * bdy),
              jnp.asarray([bdx, bdy], jnp.int32))
    flags = [_store_flags(n_warps, *common, jnp.asarray(p, jnp.int32),
                          jnp.asarray(grid, jnp.int32),
                          jnp.asarray(gmem, jnp.int32)) for p in pos]
    out = []
    for i in range(0, len(pos), 2):
        group = flags[i:i + 2] if len(pos) - i > 1 else [flags[i], flags[0]]
        hit = _group_count(group)
        out.append((len(hit), *map(int, hit.sum(0))))
    return out


def test_store_trips_reach_spans_and_registry():
    """At width 2 on 2 SMs: bitonic.256 (one block and its padded
    duplicate) stores to global memory on 1 and to shared memory on 73
    of its 619 trips; CSR SpMV at n = 1,024 (8 blocks, 4 groups) stores
    only to global memory.  Each drain fetches its counters once."""
    spmv = (spmv_csr.build(1024), *spmv_csr.launch(1024),
            spmv_csr.make_gmem(np.random.default_rng(3), 1024))
    launches = [_program("bitonic", 256), spmv]
    tracer, m = obs.Tracer().start(), obs.MetricsRegistry()
    srv = rt.RuntimeServer(n_sm=2, tracer=tracer, metrics=m)
    seen = []
    for i, (code, grid, bd, gmem) in enumerate(launches):
        srv.submit(code, grid, bd, gmem.copy(), client=f"t{i}")
        w = rt.TRANSFERS.window()
        _, stats = srv.drain()
        assert w.counter_syncs == stats.n_sub_batches == 1
        (disp,) = [d for d in tracer.find("dispatch") if d not in seen]
        seen.append(disp)
        groups = [c for c in disp.children if c.name == "device-execute"]
        got = [(g.attrs["trips"], g.attrs["gstore_trips"],
                g.attrs["sstore_trips"]) for g in groups]
        assert got == _expected_groups(code, grid, bd, gmem), i
    bitonic, csr = ([g for g in d.children if g.name == "device-execute"]
                    for d in seen)
    assert [(g.attrs["trips"], g.attrs["gstore_trips"],
             g.attrs["sstore_trips"]) for g in bitonic] == [(619, 1, 73)]
    assert len(csr) == 4 and all(g.attrs["sstore_trips"] == 0 for g in csr)
    assert all(0 < g.attrs["gstore_trips"] < g.attrs["trips"] for g in csr)
    for port in ("gstore_trips", "sstore_trips"):
        assert m.counter("smstep." + port).value == sum(
            g.attrs[port] for g in bitonic + csr)


def test_store_trips_of_the_reference_discipline_are_zero():
    """``reference`` stores in its step and has no store phase."""
    args = _bitonic_beside_reduction()
    n_warps = -(-int(args[1].max()) // isa.WARP_SIZE)
    for ctr in (_group(REF, n_warps, *args)[2],
                _one(REF, n_warps, *(a[0] for a in args))[2]):
        assert not np.asarray(store_trips(ctr)).any()
