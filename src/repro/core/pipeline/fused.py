"""Fused Pallas SM step — the whole pipeline as ONE kernel.

The paper's overlay wins by keeping the entire SIMT pipeline on-chip:
fetch, operand read, the SP array, writeback and the warp scheduler are
one pipelined datapath over block RAMs, never a sequence of separate
engines handing state through off-chip memory.  The staged all-warp
pipeline (:func:`repro.core.pipeline.sm_step`) is faithful but
substrate-unfriendly in the same way the FPGA papers warn about: five
separate stage functions materialize every intermediate (W, 32) array
between them, and only the execute stage runs as a Pallas kernel.

``execute_backend="pallas_fused"`` instead lowers the *whole* step —
barrier release + fetch/decode, register-file gather + guard LUT +
memory read ports, the shared :func:`repro.kernels.simt_alu.alu_datapath`
SP array, the write-set scatters, and the warp-stack/PC/counter update —
into a single ``pl.pallas_call``.  All architectural state lives in the
kernel's refs (VMEM on a real TPU) for the duration of the step; nothing
round-trips through HBM between stages.

Bit-exactness is by construction, not by reimplementation: the kernel
body calls the *same* stage functions (:func:`fetch_decode`,
:func:`read_operands`, :func:`write_back`, :func:`control`) on state
reconstructed from the refs, so any future stage change is picked up by
both backends and the differential suites only have to catch datatype
seams.  Those seams are exactly two: bools cross the kernel boundary as
int32 (``!= 0`` / ``astype`` on either side) and the uint32
``stack_mask`` crosses via ``lax.bitcast_convert_type`` — both are
bit-lossless.

On the CPU the kernel runs in interpret mode, which traces the body to
the same XLA ops as the staged path — what the differential suites
exercise.  The step dispatch passes ``interpret`` from
:func:`repro.core.pipeline.execute.interpret_mode`, so on the TPU the
kernel lowers through Mosaic.  Mosaic refuses it today: the fetch
gather ``code[st.pc]`` over a whole-array ref does not lower
(``ValueError: Shape mismatch in input, indices and output``), and that
error reaches whoever selected ``pallas_fused``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import isa
from .state import TRIP_SLOT, Counters, MachineConfig, SMState
from .fetch_decode import fetch_decode
from .read import read_operands
from .write import write_back
from .control import control


def _fused_step_kernel(code_ref, lut_ref, geom_ref, pc_ref, wstate_ref,
                       sp_ref, alive_ref, active_ref, saddr_ref, stype_ref,
                       smask_ref, pred_ref, regs_ref, smem_ref, gmem_ref,
                       gw_ref, cvec_ref, csca_ref,
                       pc_o, wstate_o, sp_o, alive_o, active_o, saddr_o,
                       stype_o, smask_o, pred_o, regs_o, smem_o, gmem_o,
                       gw_o, cvec_o, csca_o, *, cfg: MachineConfig):
    """One lockstep pipeline step over whole-array refs (no grid)."""
    bitcast = jax.lax.bitcast_convert_type
    cvec, csca = cvec_ref[...], csca_ref[...]
    st = SMState(
        pc=pc_ref[...],
        alive=alive_ref[...] != 0,
        active=active_ref[...] != 0,
        wstate=wstate_ref[...],
        stack_addr=saddr_ref[...],
        stack_type=stype_ref[...],
        stack_mask=bitcast(smask_ref[...], jnp.uint32),
        sp=sp_ref[...],
        pred=pred_ref[...],
        regs=regs_ref[...],
        smem=smem_ref[...],
        gmem=gmem_ref[...],
        gw=gw_ref[...] != 0,
        last_warp=jnp.zeros((), jnp.int32),   # untouched by a lockstep step
        counters=Counters(op_issues=cvec[0], op_lanes=cvec[1],
                          cycles=csca[0], stack_ops=csca[1],
                          max_sp=csca[2], overflow=csca[3]))
    geom = geom_ref[...]

    # the five stages, inlined back-to-back on in-kernel values
    dec = fetch_decode(code_ref[...], st)
    ops = read_operands(cfg, lut_ref[...] != 0, geom[0], geom[1], geom[2],
                        st, dec)
    from repro.kernels.simt_alu import alu_datapath
    result, nib = alu_datapath(
        dec.op[:, None], ops.s1, ops.s2, ops.s3, ops.cond_val, ops.s2r_val,
        ops.exec_mask, enable_mul=cfg.enable_mul,
        num_read_operands=cfg.num_read_operands)
    opb = dec.op[:, None]
    result = jnp.where(opb == isa.LDG, ops.ld_g,
                       jnp.where(opb == isa.LDS, ops.ld_s, result))
    wb = write_back(cfg, st, dec, ops, result, nib)
    (pc, alive, active, wstate, stack_addr, stack_type, stack_mask, sp,
     counters) = control(cfg, st, dec, ops)

    pc_o[...] = pc
    wstate_o[...] = wstate
    sp_o[...] = sp
    alive_o[...] = alive.astype(jnp.int32)
    active_o[...] = active.astype(jnp.int32)
    saddr_o[...] = stack_addr
    stype_o[...] = stack_type
    smask_o[...] = bitcast(stack_mask, jnp.int32)
    pred_o[...] = wb.pred
    regs_o[...] = wb.regs
    smem_o[...] = wb.smem
    gmem_o[...] = wb.gmem
    gw_o[...] = wb.gw.astype(jnp.int32)
    cvec_o[...] = jnp.stack([counters.op_issues, counters.op_lanes])
    csca_o[...] = jnp.stack([counters.cycles, counters.stack_ops,
                             counters.max_sp, counters.overflow])


def fused_sm_step(cfg: MachineConfig, code: jnp.ndarray, lut: jnp.ndarray,
                  block_dim_xy: jnp.ndarray, block_xy: jnp.ndarray,
                  grid_xy: jnp.ndarray, st: SMState, *,
                  interpret: bool) -> SMState:
    """Drop-in for :func:`sm_step` running the step as one Pallas kernel."""
    bitcast = jax.lax.bitcast_convert_type
    i32 = jnp.int32
    W, D = st.stack_addr.shape
    S1, G1 = st.smem.shape[0], st.gmem.shape[0]
    R = st.regs.shape[2]

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, i32)

    outs = pl.pallas_call(
        functools.partial(_fused_step_kernel, cfg=cfg),
        out_shape=[
            s(W), s(W), s(W),                   # pc, wstate, sp
            s(W, 32), s(W, 32),                 # alive, active
            s(W, D), s(W, D), s(W, D),          # stack addr/type/mask
            s(W, 32, 4), s(W, 32, R),           # pred, regs
            s(S1), s(G1), s(G1),                # smem, gmem, gw
            s(2, TRIP_SLOT + 1), s(4),          # counter vectors/scalars
        ],
        interpret=interpret,
    )(code, lut.astype(i32),
      jnp.stack([block_dim_xy, block_xy, grid_xy]),
      st.pc, st.wstate, st.sp,
      st.alive.astype(i32), st.active.astype(i32),
      st.stack_addr, st.stack_type, bitcast(st.stack_mask, i32),
      st.pred, st.regs, st.smem, st.gmem, st.gw.astype(i32),
      jnp.stack([st.counters.op_issues, st.counters.op_lanes]),
      jnp.stack([st.counters.cycles, st.counters.stack_ops,
                 st.counters.max_sp, st.counters.overflow]))

    (pc, wstate, sp, alive, active, stack_addr, stack_type, stack_mask,
     pred, regs, smem, gmem, gw, cvec, csca) = outs
    return SMState(
        pc=pc, alive=alive != 0, active=active != 0, wstate=wstate,
        stack_addr=stack_addr, stack_type=stack_type,
        stack_mask=bitcast(stack_mask, jnp.uint32), sp=sp,
        pred=pred, regs=regs, smem=smem, gmem=gmem, gw=gw != 0,
        last_warp=st.last_warp,
        counters=Counters(op_issues=cvec[0], op_lanes=cvec[1],
                          cycles=csca[0], stack_ops=csca[1],
                          max_sp=csca[2], overflow=csca[3]))
