"""The FlexGrip-JAX streaming multiprocessor as a five-stage package.

The paper's SM pipeline — Fetch/Decode, Read, Execute, Write plus the
control unit — is one module per stage:

* :mod:`fetch_decode` — barrier release, all-warp instruction fetch,
  field decode, ``.S`` reconvergence pop;
* :mod:`read`         — operand units, guard LUT, S2R, memory read ports;
* :mod:`execute`      — the SP array's ALU;
* :mod:`write`        — register/predicate writeback, and the
  global/shared stores that the machine loop's store phase applies;
* :mod:`control`      — warp stack, EXIT/BAR, next PC, counters;
* :mod:`reference`    — the seed one-warp-per-issue interpreter, kept as
  the equivalence oracle (``execute_backend="reference"``).

Issue discipline: where the seed interpreter issued ONE warp per
``lax.while_loop`` iteration, :func:`sm_step` issues the instruction of
EVERY ready warp simultaneously over the (W, 32) lane grid — the
lockstep all-warp pipeline that keeps the vector substrate busy, while
per-warp cycle accounting still charges the seed's serialized-issue
cost so paper-faithful timing is unchanged (see :mod:`control`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...obs import jit_call
from .. import isa
from .state import (EXECUTE_BACKENDS, FINISHED, GSTORE_SLOT, READY,
                    SSTORE_SLOT, TRIP_SLOT, WAIT, Counters, MachineConfig,
                    SMState, _LANES, _pack, _unpack, init_state)
from .fetch_decode import Decoded, fetch_decode
from .read import Operands, read_operands
from .execute import execute
from .write import store_global, store_shared, write_back
from .control import control
from .reference import issue_one_warp

__all__ = [
    "EXECUTE_BACKENDS", "READY", "WAIT", "FINISHED", "Counters",
    "Decoded", "MachineConfig", "Operands", "SMState", "TRIP_SLOT",
    "GSTORE_SLOT", "SSTORE_SLOT", "sm_step", "issue_one_warp",
    "init_state", "run_block", "run_block_body", "split_trips",
    "store_trips", "step_fn",
    "block_running", "_run_block_jit", "_LANES", "_pack",
    "_unpack",
]


def sm_stage(cfg: MachineConfig, code: jnp.ndarray, lut: jnp.ndarray,
             block_dim_xy: jnp.ndarray, block_xy: jnp.ndarray,
             grid_xy: jnp.ndarray, st: SMState):
    """One lockstep step but its memory stores: every READY warp runs
    the full pipeline.  Returns the state, its memories as they came in,
    and the trip's global and shared :class:`Store` requests, which
    :func:`store_phase` applies.

    Each stage runs under a ``jax.named_scope`` of its name, so the ops
    of a device trace say which stage they belong to."""
    with jax.named_scope("fetch_decode"):
        dec = fetch_decode(code, st)
    with jax.named_scope("read_operands"):
        ops = read_operands(cfg, lut, block_dim_xy, block_xy, grid_xy, st,
                            dec)
    with jax.named_scope("execute"):
        result, nib_new = execute(cfg, dec, ops)
    with jax.named_scope("write_back"):
        wb = write_back(cfg, st, dec, ops, result, nib_new)
    with jax.named_scope("control"):
        (pc, alive, active, wstate, stack_addr, stack_type, stack_mask, sp,
         counters) = control(cfg, st, dec, ops)
    return st._replace(
        pc=pc, alive=alive, active=active, wstate=wstate,
        stack_addr=stack_addr, stack_type=stack_type,
        stack_mask=stack_mask, sp=sp, pred=wb.pred, regs=wb.regs,
        counters=counters), (wb.glob, wb.shared)


def store_phase(st: SMState, stores, batched: bool = False):
    """A trip's memory stores: each port's scatter guarded by whether
    any lane stores to it, of any block when ``batched`` (a leading
    block axis).  The guard is never batched, so it stays a branch and
    is not turned into a select of both sides, which would copy the
    memory row.  Returns the state and ``(2,)`` int32, 1 where the
    global / shared scatter ran.  ``stores`` ``None`` (the
    ``reference`` discipline, which stores in its step) applies
    nothing."""
    if stores is None:
        return st, jnp.zeros((2,), jnp.int32)
    glob, shared = stores
    g_fn, s_fn = store_global, store_shared
    if batched:
        g_fn, s_fn = jax.vmap(g_fn), jax.vmap(s_fn)
    run_g, run_s = jnp.any(glob.mask), jnp.any(shared.mask)
    with jax.named_scope("store_global"):
        gmem, gw = _guarded(run_g, lambda gm, gw: g_fn(gm, gw, glob),
                            st.gmem, st.gw)
    with jax.named_scope("store_shared"):
        (smem,) = _guarded(run_s, lambda sm: (s_fn(sm, shared),), st.smem)
    return (st._replace(gmem=gmem, gw=gw, smem=smem),
            jnp.stack([run_g, run_s]).astype(jnp.int32))


def _guarded(run, fn, *rows):
    """``fn(*rows)`` if ``run``, else ``rows``, as a loop of at most one
    trip.  A ``lax.cond`` would branch the same way, but the TPU keeps a
    conditional's operands in HBM: the memory rows, which the machine
    loop keeps in on-chip memory where they fit, would be copied out and
    back on every trip, taken or not (``tests/test_tpu_compile.py``)."""
    return jax.lax.while_loop(lambda c: c[0],
                              lambda c: (jnp.bool_(False), fn(*c[1])),
                              (run, rows))[1]


def sm_step(cfg: MachineConfig, code: jnp.ndarray, lut: jnp.ndarray,
            block_dim_xy: jnp.ndarray, block_xy: jnp.ndarray,
            grid_xy: jnp.ndarray, st: SMState) -> SMState:
    """One lockstep step: :func:`sm_stage`, then its stores."""
    return store_phase(*sm_stage(cfg, code, lut, block_dim_xy, block_xy,
                                 grid_xy, st))[0]


def run_block_body(cfg: MachineConfig, n_warps: int, code, block_dim,
                   block_dim_xy, block_xy, grid_xy, gmem):
    """The machine loop: run one block to completion, W static.

    ``block_dim`` may be a Python int or a traced scalar — the device
    runtime passes it traced so one compiled machine serves any tenant:
    warps beyond a launch's real thread count initialize FINISHED and
    never issue, keeping counters bit-exact at any warp padding.
    Returns ``(gmem, written-mask, loop counters)`` with the
    store-sentinel word stripped.  The loop counters still hold the
    :data:`TRIP_SLOT` entry and, past it, the trips on which the loop's
    store phase ran each port (:data:`GSTORE_SLOT`, :data:`SSTORE_SLOT`,
    read by :func:`store_trips`); :func:`split_trips` takes the loop's
    trip count out of them, and every caller does so before a
    ``Counters`` leaves it.

    Each trip is :func:`sm_stage` then :func:`store_phase`, so a port's
    scatter runs only on a trip where some lane stores to it.  Under
    ``jax.vmap`` — a dispatch group's blocks — the blocks run in one
    loop of their own (:func:`_group_loop`), not in a batched per-block
    loop, and the store counts are the group's.
    """
    return _machine_loop(cfg, n_warps)(
        code, jnp.asarray(block_dim, jnp.int32), block_dim_xy, block_xy,
        grid_xy, gmem)


def _machine_loop(cfg: MachineConfig, n_warps: int):
    """:func:`run_block_body`'s loop for one ``(cfg, n_warps)``: a plain
    per-block loop, batched by :func:`_group_loop`."""
    @jax.custom_batching.custom_vmap
    def loop(code, block_dim, block_dim_xy, block_xy, grid_xy, gmem):
        lut = jnp.asarray(isa.COND_LUT)
        stage = functools.partial(_stage_fn(cfg), cfg, code, lut,
                                  block_dim_xy, block_xy, grid_xy)

        def body(carry):
            st, n = carry
            st, ran = store_phase(*stage(st))
            return st, n + ran

        st, n = jax.lax.while_loop(
            lambda carry: block_running(cfg, carry[0]), body,
            (init_state(cfg, n_warps, block_dim, gmem),
             jnp.zeros((2,), jnp.int32)))
        return st.gmem[:-1], st.gw[:-1], _with_store_trips(st.counters, n)

    @loop.def_vmap
    def group(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size, *a.shape))
                for a, b in zip(args, in_batched)]
        st, n = _group_loop(cfg, n_warps, *args)
        out = (st.gmem[:, :-1], st.gw[:, :-1],
               _with_store_trips(st.counters, jnp.broadcast_to(
                   n, (axis_size, 2))))
        return out, jax.tree.map(lambda _: True, out)

    return loop


#: The leaves the store phase rewrites by scatter.  The group loop takes
#: them from it as they come: a select between a block's old and new
#: memory would keep the old row live, and the scatter would then copy
#: the whole row every trip instead of writing in place.
_MEMORY = ("smem", "gmem", "gw")


def _group_loop(cfg: MachineConfig, n_warps: int, code, block_dim,
                block_dim_xy, block_xy, grid_xy, gmem):
    """A dispatch group's blocks (leading axis) in one machine loop;
    returns the final state and ``(2,)`` int32, the trips on which the
    group's global / shared store phase ran.

    The loop runs while any block runs (:func:`block_running`, per
    block) and steps every block each trip: the stage under ``vmap``,
    then one :func:`store_phase` for the group, each port's scatter
    guarded by whether any lane of any block stores to it.  A
    stopped block is stepped parked: every warp FINISHED and no thread
    alive or active, so it issues nothing and, in a trip whose scatter
    runs for another block, each of its stores rewrites a sentinel word
    with that word's own value — its memory comes out unchanged.  Its
    other leaves, counters included, are restored by a select, so its
    trips, cycles and counts stop where the per-block loop stops them,
    the runaway guard included."""
    lut = jnp.asarray(isa.COND_LUT)
    stage = jax.vmap(functools.partial(_stage_fn(cfg), cfg),
                     in_axes=(0, None, 0, 0, 0, 0))
    running = jax.vmap(functools.partial(block_running, cfg))

    def body(carry):
        st, n = carry
        go = running(st)
        parked = st._replace(
            wstate=jnp.where(go[:, None], st.wstate, FINISHED),
            alive=st.alive & go[:, None, None],
            active=st.active & go[:, None, None])
        new, stores = stage(code, lut, block_dim_xy, block_xy, grid_xy,
                            parked)
        new, ran = store_phase(new, stores, batched=True)

        def keep(a, b):
            return jnp.where(go.reshape(go.shape + (1,) * (a.ndim - 1)),
                             a, b)
        return new._replace(**{
            f: jax.tree.map(keep, getattr(new, f), getattr(st, f))
            for f in SMState._fields if f not in _MEMORY}), n + ran

    st0 = jax.vmap(functools.partial(init_state, cfg, n_warps))(block_dim,
                                                                gmem)
    return jax.lax.while_loop(lambda carry: jnp.any(running(carry[0])),
                              body, (st0, jnp.zeros((2,), jnp.int32)))


def _with_store_trips(ctr: Counters, n) -> Counters:
    """The loop counters with the store-phase trips ``n`` (``(..., 2)``)
    at :data:`GSTORE_SLOT` / :data:`SSTORE_SLOT`."""
    return ctr._replace(op_issues=jnp.concatenate([ctr.op_issues, n], -1))


def split_trips(cfg: MachineConfig, n_warps: int, ctr: Counters):
    """``(Counters, trips)`` from the machine loop's counters, batched or
    not, on the device or on the host: the opcode vectors without
    :data:`TRIP_SLOT`, and how many times the loop ran its step.  The
    trips stay out of ``Counters`` because they depend on the issue
    discipline (``reference`` visits one warp a step, the lockstep step
    all ``n_warps``)."""
    warps_per_step = 1 if cfg.execute_backend == "reference" else n_warps
    return (ctr._replace(op_issues=ctr.op_issues[..., :TRIP_SLOT],
                         op_lanes=ctr.op_lanes[..., :TRIP_SLOT]),
            ctr.op_issues[..., :TRIP_SLOT + 1].sum(axis=-1)
            // warps_per_step)


def store_trips(ctr: Counters):
    """``(..., 2)`` from the machine loop's counters: the trips on which
    its global and its shared store phase ran (its group's, under
    ``vmap``).  Always 0 under ``reference``, which stores in its step."""
    return ctr.op_issues[..., GSTORE_SLOT:SSTORE_SLOT + 1]


def step_fn(cfg: MachineConfig):
    """The step function of ``cfg.execute_backend``, called as
    ``step(cfg, code, lut, block_dim_xy, block_xy, grid_xy, st)``."""
    return issue_one_warp if cfg.execute_backend == "reference" else sm_step


def _stage_fn(cfg: MachineConfig):
    """The machine loop's stage of ``cfg.execute_backend``: called as
    :func:`step_fn`'s step, it returns ``(state, stores)`` for
    :func:`store_phase`.  ``reference`` stores in its step and has no
    store phase."""
    if cfg.execute_backend == "reference":
        return lambda *args: (issue_one_warp(*args), None)
    return sm_stage


def block_running(cfg: MachineConfig, st: SMState):
    """The machine loop's condition: a warp is not FINISHED and the
    runaway guard has not tripped."""
    return jnp.any(st.wstate != FINISHED) & \
        (st.counters.cycles < cfg.max_cycles)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _run_block_jit(cfg: MachineConfig, code: jnp.ndarray, block_dim: int,
                   block_dim_xy: jnp.ndarray, block_xy: jnp.ndarray,
                   grid_xy: jnp.ndarray, gmem: jnp.ndarray):
    n_warps = -(-block_dim // isa.WARP_SIZE)
    gm, gw, ctr = run_block_body(cfg, n_warps, code, block_dim,
                                 block_dim_xy, block_xy, grid_xy, gmem)
    return gm, gw, split_trips(cfg, n_warps, ctr)[0]


def run_block(code, block_dim: int, block_xy, grid_xy, gmem,
              cfg: MachineConfig = MachineConfig()):
    """Execute one thread block; returns (gmem, written-mask, Counters).

    ``block_dim`` may be an int (1-D block) or an (x, y) tuple.
    """
    if isinstance(block_dim, tuple):
        bdx, bdy = block_dim
    else:
        bdx, bdy = block_dim, 1
    code = jnp.asarray(code, jnp.int32)
    gmem = jnp.asarray(gmem, jnp.int32)
    bucket = f"c{code.shape[0]}g{gmem.shape[0]}b{bdx * bdy}"
    with jit_call("pipeline.run_block", _run_block_jit, bucket=bucket,
                  key=(cfg, code.shape, bdx * bdy, gmem.shape)):
        return _run_block_jit(
            cfg, code, bdx * bdy,
            jnp.asarray([bdx, bdy], jnp.int32),
            jnp.asarray(block_xy, jnp.int32),
            jnp.asarray(grid_xy, jnp.int32),
            gmem)
