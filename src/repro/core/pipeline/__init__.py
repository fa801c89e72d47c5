"""The FlexGrip-JAX streaming multiprocessor as a five-stage package.

The paper's SM pipeline — Fetch/Decode, Read, Execute, Write plus the
control unit — is one module per stage:

* :mod:`fetch_decode` — barrier release, all-warp instruction fetch,
  field decode, ``.S`` reconvergence pop;
* :mod:`read`         — operand units, guard LUT, S2R, memory read ports;
* :mod:`execute`      — the pluggable SP-array backend (pure jnp or the
  Pallas ``simt_alu`` VPU kernel);
* :mod:`write`        — register/predicate writeback, global/shared
  stores;
* :mod:`control`      — warp stack, EXIT/BAR, next PC, counters;
* :mod:`fused`        — the whole step as ONE Pallas kernel
  (``execute_backend="pallas_fused"``): same stage functions traced
  inside a single ``pallas_call`` so no intermediate (W, 32) arrays are
  materialized between stages;
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
from .state import (EXECUTE_BACKENDS, FINISHED, READY, TRIP_SLOT, WAIT,
                    Counters, MachineConfig, SMState, _BITS, _LANES, _pack,
                    _unpack, init_state)
from .fetch_decode import Decoded, fetch_decode
from .read import Operands, read_operands
from .execute import EXECUTE_STAGE_BACKENDS, execute, interpret_mode
from .write import write_back
from .control import control
from .fused import fused_sm_step
from .reference import issue_one_warp

__all__ = [
    "EXECUTE_BACKENDS", "EXECUTE_STAGE_BACKENDS", "READY", "WAIT",
    "FINISHED", "Counters", "Decoded", "MachineConfig", "Operands",
    "SMState", "TRIP_SLOT", "sm_step", "fused_sm_step", "issue_one_warp",
    "init_state", "run_block", "run_block_body", "split_trips", "step_fn",
    "block_running", "_run_block_jit", "_BITS", "_LANES", "_pack",
    "_unpack",
]


def sm_step(cfg: MachineConfig, code: jnp.ndarray, lut: jnp.ndarray,
            block_dim_xy: jnp.ndarray, block_xy: jnp.ndarray,
            grid_xy: jnp.ndarray, st: SMState) -> SMState:
    """One lockstep step: every READY warp runs the full pipeline.

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
    return SMState(
        pc=pc, alive=alive, active=active, wstate=wstate,
        stack_addr=stack_addr, stack_type=stack_type,
        stack_mask=stack_mask, sp=sp,
        pred=wb.pred, regs=wb.regs, smem=wb.smem, gmem=wb.gmem, gw=wb.gw,
        last_warp=st.last_warp, counters=counters)


def run_block_body(cfg: MachineConfig, n_warps: int, code, block_dim,
                   block_dim_xy, block_xy, grid_xy, gmem):
    """The machine loop: run one block to completion, W static.

    ``block_dim`` may be a Python int or a traced scalar — the device
    runtime passes it traced so one compiled machine serves any tenant:
    warps beyond a launch's real thread count initialize FINISHED and
    never issue, keeping counters bit-exact at any warp padding.
    Returns ``(gmem, written-mask, loop counters)`` with the
    store-sentinel word stripped.  The loop counters still hold the
    :data:`TRIP_SLOT` entry; :func:`split_trips` takes the loop's trip
    count out of them, and every caller does so before a ``Counters``
    leaves it.

    Under ``jax.vmap`` — a dispatch group's blocks — the blocks run in
    one loop of their own (:func:`_group_loop`), not in a batched
    per-block loop.
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
        body = functools.partial(step_fn(cfg), cfg, code, lut, block_dim_xy,
                                 block_xy, grid_xy)
        st = jax.lax.while_loop(functools.partial(block_running, cfg), body,
                                init_state(cfg, n_warps, block_dim, gmem))
        return st.gmem[:-1], st.gw[:-1], st.counters

    @loop.def_vmap
    def group(axis_size, in_batched, *args):
        args = [a if b else jnp.broadcast_to(a, (axis_size, *a.shape))
                for a, b in zip(args, in_batched)]
        st = _group_loop(cfg, n_warps, *args)
        out = st.gmem[:, :-1], st.gw[:, :-1], st.counters
        return out, jax.tree.map(lambda _: True, out)

    return loop


#: The leaves a step rewrites by scatter.  The group loop takes them
#: from the step as they come: a select between a block's old and new
#: memory would keep the old row live, and the scatter would then copy
#: the whole row every trip instead of writing in place.
_MEMORY = ("smem", "gmem", "gw")


def _group_loop(cfg: MachineConfig, n_warps: int, code, block_dim,
                block_dim_xy, block_xy, grid_xy, gmem) -> SMState:
    """A dispatch group's blocks (leading axis) in one machine loop.

    The loop runs while any block runs (:func:`block_running`, per
    block) and steps every block each trip.  A stopped block is stepped
    parked: every warp FINISHED and no thread alive or active, so it
    issues nothing and each of its stores rewrites a sentinel word with
    that word's own value — its memory comes out of the step unchanged.
    Its other leaves, counters included, are restored by a select, so
    its trips, cycles and counts stop where the per-block loop stops
    them, the runaway guard included."""
    lut = jnp.asarray(isa.COND_LUT)
    step = jax.vmap(functools.partial(step_fn(cfg), cfg),
                    in_axes=(0, None, 0, 0, 0, 0))
    running = jax.vmap(functools.partial(block_running, cfg))

    def body(st):
        go = running(st)
        parked = st._replace(
            wstate=jnp.where(go[:, None], st.wstate, FINISHED),
            alive=st.alive & go[:, None, None],
            active=st.active & go[:, None, None])
        new = step(code, lut, block_dim_xy, block_xy, grid_xy, parked)

        def keep(a, b):
            return jnp.where(go.reshape(go.shape + (1,) * (a.ndim - 1)),
                             a, b)
        return new._replace(**{
            f: jax.tree.map(keep, getattr(new, f), getattr(st, f))
            for f in SMState._fields if f not in _MEMORY})

    st0 = jax.vmap(functools.partial(init_state, cfg, n_warps))(block_dim,
                                                                gmem)
    return jax.lax.while_loop(lambda st: jnp.any(running(st)), body, st0)


def split_trips(cfg: MachineConfig, n_warps: int, ctr: Counters):
    """``(Counters, trips)`` from the machine loop's counters, batched or
    not, on the device or on the host: the opcode vectors without
    :data:`TRIP_SLOT`, and how many times the loop ran its step.  The
    trips stay out of ``Counters`` because they depend on the issue
    discipline (``reference`` visits one warp a step, the lockstep
    backends all ``n_warps``)."""
    warps_per_step = 1 if cfg.execute_backend == "reference" else n_warps
    return (ctr._replace(op_issues=ctr.op_issues[..., :TRIP_SLOT],
                         op_lanes=ctr.op_lanes[..., :TRIP_SLOT]),
            ctr.op_issues.sum(axis=-1) // warps_per_step)


def step_fn(cfg: MachineConfig):
    """The step function of ``cfg.execute_backend``, called as
    ``step(cfg, code, lut, block_dim_xy, block_xy, grid_xy, st)``."""
    if cfg.execute_backend == "reference":
        return issue_one_warp
    if cfg.execute_backend == "pallas_fused":
        return functools.partial(fused_sm_step, interpret=interpret_mode())
    return sm_step


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
