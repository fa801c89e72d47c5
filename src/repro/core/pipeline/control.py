"""Control stage of the all-warp pipeline.

Per-warp control flow, vectorized over the warp axis: divergent-branch
bookkeeping on the warp stack (SSY pushes a reconvergence entry, a
divergent BRA pushes the taken path and runs not-taken first — Fig. 2),
EXIT retirement with pending-path resume, block barriers, next-PC
selection, and the cycle/issue counters.

Cycle accounting is deliberately the *seed's serialized-issue model*:
each issuing warp is charged ``rows_per_warp`` (+ memory latency) as if
the single issue path dispatched it alone, so total cycles — and with
them every paper-faithful timing result (Fig. 4/5, Tables 3/5/6) — are
bit-identical to the one-warp-per-iteration interpreter even though the
substrate now executes all warps per step.
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import isa
from .state import FINISHED, TRIP_SLOT, WAIT, Counters, MachineConfig, \
    SMState, _pack, _unpack
from .fetch_decode import Decoded
from .read import Operands

def control(cfg: MachineConfig, st: SMState, dec: Decoded, ops: Operands):
    """Returns (pc, alive, active, wstate, stack_addr, stack_type,
    stack_mask, sp, counters) — the post-issue control state."""
    W = st.pc.shape[0]
    arange_w = jnp.arange(W, dtype=jnp.int32)

    part = dec.active & st.alive & dec.exec_this[:, None]
    # BRA condition comes from the guard LUT; an unguarded BRA is taken by
    # every participating lane.
    taken = jnp.where(dec.guarded[:, None], part & ops.cond_val, part)
    ntk = part & ~taken
    any_t = jnp.any(taken, axis=1)
    any_n = jnp.any(ntk, axis=1)

    is_bra = (dec.op == isa.BRA) & dec.exec_this
    is_ssy = (dec.op == isa.SSY) & dec.exec_this
    diverge = is_bra & any_t & any_n
    uni_taken = is_bra & any_t & ~any_n

    # pushes: SSY pushes (RECONV, reconv_addr, current mask);
    # a divergent BRA pushes (TAKEN, target, taken mask) — not-taken first.
    do_push = diverge | is_ssy
    push_type = jnp.where(is_ssy, isa.STACK_RECONV, isa.STACK_TAKEN)
    push_mask = _pack(jnp.where(is_ssy[:, None], part, taken))
    slot = jnp.clip(dec.sp, 0, cfg.warp_stack_depth - 1)
    stack_addr = st.stack_addr.at[arange_w, slot].set(
        jnp.where(do_push, dec.imm, st.stack_addr[arange_w, slot]))
    stack_type = st.stack_type.at[arange_w, slot].set(
        jnp.where(do_push, push_type, st.stack_type[arange_w, slot]))
    stack_mask = st.stack_mask.at[arange_w, slot].set(
        jnp.where(do_push, push_mask, st.stack_mask[arange_w, slot]))
    overflow_now = do_push & (dec.sp >= cfg.warp_stack_depth)
    sp_new = dec.sp + jnp.where(do_push, 1, 0)

    # ---- EXIT ------------------------------------------------------------
    is_exit = (dec.op == isa.EXIT) & dec.exec_this
    alive_new = jnp.where(is_exit[:, None], st.alive & ~ops.exec_mask,
                          st.alive)
    warp_done = is_exit & ~jnp.any(alive_new, axis=1)
    # EXIT with survivors resumes a pending path from the stack
    exit_resume = is_exit & ~warp_done & (sp_new > 0)
    etop = jnp.maximum(sp_new - 1, 0)
    e_addr = stack_addr[arange_w, etop]
    e_type = stack_type[arange_w, etop]
    e_mask = _unpack(stack_mask[arange_w, etop])
    sp_new = sp_new - jnp.where(exit_resume, 1, 0)
    active_new = jnp.where(
        exit_resume[:, None], e_mask & alive_new,
        jnp.where(diverge[:, None], ntk,
                  jnp.where(is_exit[:, None], alive_new, dec.active)))

    # ---- next PC ----------------------------------------------------------
    resume_jump = exit_resume & (e_type == isa.STACK_TAKEN)
    pc_next = jnp.where(
        dec.pop_taken, dec.top_addr,
        jnp.where(uni_taken, dec.imm,
                  jnp.where(resume_jump, e_addr, st.pc + 1)))
    pc = jnp.where(dec.issued, pc_next, st.pc)
    # BAR: wait at the *next* instruction
    is_bar = (dec.op == isa.BAR) & dec.exec_this
    wstate = jnp.where(warp_done, FINISHED,
                       jnp.where(is_bar, WAIT, dec.wstate))

    # ---- counters / cycle cost -------------------------------------------
    # scalar opcode bitmasks, not array table gathers: this stage is
    # also traced inside the fused Pallas kernel (fused.py), which
    # rejects captured array constants
    is_gmem = ((jnp.int32(isa.IS_GMEM_MASK) >> dec.op) & 1) != 0
    is_smem = ((jnp.int32(isa.IS_SMEM_MASK) >> dec.op) & 1) != 0
    cost = jnp.where(
        dec.issued,
        jnp.where(
            dec.exec_this,
            cfg.rows_per_warp
            + jnp.where(is_gmem, cfg.mem_latency_global, 0)
            + jnp.where(is_smem, cfg.mem_latency_shared, 0),
            1),                              # a TAKEN pop costs one cycle
        0)                                   # non-issued warps: idle
    c = st.counters
    # a warp that does not execute counts at TRIP_SLOT: every step adds
    # W issues in all, which is how the loop's trips are counted
    op_c = jnp.where(dec.exec_this, dec.op, TRIP_SLOT)
    counters = Counters(
        op_issues=c.op_issues.at[op_c].add(1),
        op_lanes=c.op_lanes.at[op_c].add(
            jnp.sum(ops.exec_mask, axis=1).astype(jnp.int32)),
        cycles=c.cycles + jnp.sum(cost),
        stack_ops=c.stack_ops + jnp.sum(
            do_push.astype(jnp.int32) + dec.do_pop.astype(jnp.int32)
            + exit_resume.astype(jnp.int32)),
        max_sp=jnp.maximum(c.max_sp, jnp.max(sp_new)),
        overflow=c.overflow | jnp.any(overflow_now).astype(jnp.int32))

    return (pc, alive_new, active_new, wstate, stack_addr, stack_type,
            stack_mask, sp_new, counters)
