"""The seed one-warp-per-issue interpreter, kept bit-for-bit.

This is the original ``machine._issue``: each ``lax.while_loop``
iteration performs ONE scheduler issue — the round-robin pick of a
single ready warp and its full Fetch/Decode/Read/Execute/Write pass.
It is retained verbatim under ``MachineConfig.execute_backend=
"reference"`` as the semantic oracle the lockstep all-warp pipeline is
property-tested against (same final gmem, same per-opcode issue/lane
counters, same cycles), and as the faithful model of the paper's
single-issue-path SM for anyone studying the microarchitecture.
"""
from __future__ import annotations

import jax.numpy as jnp

from .. import isa
from .state import FINISHED, READY, TRIP_SLOT, WAIT, Counters, \
    MachineConfig, SMState, _LANES, _pack, _unpack


def issue_one_warp(cfg: MachineConfig, code: jnp.ndarray,
                   lut: jnp.ndarray, block_dim_xy: jnp.ndarray,
                   block_xy: jnp.ndarray, grid_xy: jnp.ndarray,
                   st: SMState) -> SMState:
    """One scheduler issue — the whole 5-stage pipeline for one warp."""
    W = st.pc.shape[0]
    G = st.gmem.shape[0] - 1

    # ---- barrier release: if nothing is ready, wake all BAR waiters
    ready = st.wstate == READY
    none_ready = ~jnp.any(ready)
    wstate = jnp.where(none_ready & (st.wstate == WAIT), READY, st.wstate)
    ready = wstate == READY

    # ---- warp scheduler: round-robin pick of the next ready warp
    order = (st.last_warp + 1 + jnp.arange(W, dtype=jnp.int32)) % W
    w = order[jnp.argmax(ready[order])]

    # ---- Fetch
    pc_w = st.pc[w]
    instr = code[pc_w]
    # ---- Decode
    op = instr[isa.F_OP]
    dst = instr[isa.F_DST]
    src1 = instr[isa.F_SRC1]
    src2 = instr[isa.F_SRC2]
    src3 = instr[isa.F_SRC3]
    imm = instr[isa.F_IMM]
    flags = instr[isa.F_FLAGS]
    gpred = instr[isa.F_GPRED]
    gcond = instr[isa.F_GCOND]
    pdst = instr[isa.F_PDST]

    alive_w = st.alive[w]
    active_w = st.active[w]
    sp_w = st.sp[w]

    # ---- reconvergence-point pop (.S), §4.1 / Fig. 2 ------------------
    top = jnp.maximum(sp_w - 1, 0)
    top_addr = st.stack_addr[w, top]
    top_type = st.stack_type[w, top]
    top_mask = _unpack(st.stack_mask[w, top])
    do_pop = ((flags & isa.FLAG_SYNC) != 0) & (sp_w > 0)
    pop_taken = do_pop & (top_type == isa.STACK_TAKEN)
    # TAKEN pop: jump to the stored taken address with the stored mask and
    # spend this cycle on the jump.  RECONV pop: restore the pre-divergence
    # mask and execute this instruction in the same issue.
    active_w = jnp.where(do_pop, top_mask, active_w)
    sp_w = sp_w - jnp.where(do_pop, 1, 0)
    exec_this = ~pop_taken

    # ---- guard / condition evaluation (predicate LUT of Fig. 2) -------
    pred_w = st.pred[w]                                  # (32, 4)
    nib = pred_w[_LANES, gpred]                          # (32,)
    cond_val = lut[gcond, nib]                           # (32,) bool
    guarded = (flags & isa.FLAG_GUARD) != 0
    gm = jnp.where(guarded, cond_val, True)
    exec_mask = active_w & alive_w & gm & exec_this

    # ---- Read stage: parallel source-operand units (§4.2) -------------
    regs_w = st.regs[w]                                  # (32, R)
    s1 = jnp.where((flags & isa.FLAG_SRC1_IMM) != 0, imm,
                   regs_w[_LANES, src1])
    s2 = jnp.where((flags & isa.FLAG_SRC2_IMM) != 0, imm,
                   regs_w[_LANES, src2])
    s3 = regs_w[_LANES, src3] if cfg.num_read_operands >= 3 \
        else jnp.zeros_like(s1)

    # ---- special-register values for S2R -------------------------------
    tid_flat = w * 32 + _LANES
    bdx, bdy = block_dim_xy[0], block_dim_xy[1]
    srs = jnp.stack([
        tid_flat % bdx, tid_flat // bdx,          # tidx, tidy
        jnp.broadcast_to(block_xy[0], (32,)),     # ctax
        jnp.broadcast_to(block_xy[1], (32,)),     # ctay
        jnp.broadcast_to(bdx, (32,)),             # ntidx
        jnp.broadcast_to(bdy, (32,)),             # ntidy
        jnp.broadcast_to(grid_xy[0], (32,)),      # nctax
        jnp.broadcast_to(grid_xy[1], (32,)),      # nctay
        tid_flat,                                 # flat tid
        jnp.broadcast_to(block_xy[1] * grid_xy[0] + block_xy[0], (32,)),
        jnp.broadcast_to(bdx * bdy, (32,)),       # flat block size
    ]).astype(jnp.int32)
    s2r_val = srs[jnp.clip(imm, 0, srs.shape[0] - 1)]

    # ---- Execute stage: vector ALU (compute all, select by opcode) ----
    sh = s2 & 31
    u1 = s1.astype(jnp.uint32)
    mul_lo = (s1 * s2) if cfg.enable_mul else jnp.zeros_like(s1)
    mad = (s1 * s2 + s3) if (cfg.enable_mul and
                             cfg.num_read_operands >= 3) \
        else jnp.zeros_like(s1)
    addr = s1 + imm                                      # memory address
    gaddr = jnp.clip(addr, 0, G - 1)
    saddr = jnp.clip(addr, 0, cfg.smem_words - 1)
    ld_g = st.gmem[gaddr]
    ld_s = st.smem[saddr]

    # ISETP flags of (s1 - s2): sign, zero, carry(borrow), overflow
    diff = s1 - s2
    f_s = (diff < 0).astype(jnp.int32)
    f_z = (diff == 0).astype(jnp.int32)
    f_c = (u1 < s2.astype(jnp.uint32)).astype(jnp.int32)
    f_o = (((s1 ^ s2) & (s1 ^ diff)) < 0).astype(jnp.int32)
    nib_new = f_s | (f_z << 1) | (f_c << 2) | (f_o << 3)

    result = jnp.select(
        [op == o for o in (isa.MOV, isa.IADD, isa.ISUB, isa.IMUL, isa.IMAD,
                           isa.IMIN, isa.IMAX, isa.IABS, isa.AND, isa.OR,
                           isa.XOR, isa.NOT, isa.SHL, isa.SHR, isa.SAR,
                           isa.ISET, isa.SELP, isa.S2R, isa.LDG, isa.LDS)],
        [s2, s1 + s2, s1 - s2, mul_lo, mad,
         jnp.minimum(s1, s2), jnp.maximum(s1, s2), jnp.abs(s1),
         s1 & s2, s1 | s2,
         s1 ^ s2, ~s1, (u1 << sh.astype(jnp.uint32)).astype(jnp.int32),
         (u1 >> sh.astype(jnp.uint32)).astype(jnp.int32), s1 >> sh,
         cond_val.astype(jnp.int32), jnp.where(cond_val, s1, s2), s2r_val,
         ld_g, ld_s],
        jnp.zeros_like(s1))

    # ---- Write stage ----------------------------------------------------
    has_dst = jnp.asarray(isa.WRITES_REG)[op]
    wr = exec_mask & has_dst
    new_dcol = jnp.where(wr, result, regs_w[_LANES, dst])
    regs = st.regs.at[w, _LANES, dst].set(new_dcol)

    is_setp = op == isa.ISETP
    new_pcol = jnp.where(exec_mask & is_setp, nib_new, pred_w[_LANES, pdst])
    pred = st.pred.at[w, _LANES, pdst].set(new_pcol)

    # global / shared stores (inactive lanes write the sentinel word)
    st_g = exec_mask & (op == isa.STG)
    gidx = jnp.where(st_g, gaddr, G)
    gmem = st.gmem.at[gidx].set(jnp.where(st_g, s2, st.gmem[gidx]))
    gwrt = st.gw.at[gidx].set(st.gw[gidx] | st_g)

    st_s = exec_mask & (op == isa.STS)
    sidx = jnp.where(st_s, saddr, cfg.smem_words - 1)
    smem = st.smem.at[sidx].set(jnp.where(st_s, s2, st.smem[sidx]))

    # ---- control flow ----------------------------------------------------
    part = active_w & alive_w & exec_this      # lanes participating in BRA
    # BRA condition comes from the guard LUT; an unguarded BRA is taken by
    # every participating lane.
    taken = jnp.where(guarded, part & cond_val, part)
    ntk = part & ~taken
    any_t = jnp.any(taken)
    any_n = jnp.any(ntk)

    is_bra = (op == isa.BRA) & exec_this
    is_ssy = (op == isa.SSY) & exec_this
    diverge = is_bra & any_t & any_n
    uni_taken = is_bra & any_t & ~any_n

    # pushes: SSY pushes (RECONV, reconv_addr, current mask);
    # a divergent BRA pushes (TAKEN, target, taken mask) — not-taken first.
    do_push = diverge | is_ssy
    push_type = jnp.where(is_ssy, isa.STACK_RECONV, isa.STACK_TAKEN)
    push_mask = _pack(jnp.where(is_ssy, part, taken))
    slot = jnp.clip(sp_w, 0, cfg.warp_stack_depth - 1)
    stack_addr = st.stack_addr.at[w, slot].set(
        jnp.where(do_push, imm, st.stack_addr[w, slot]))
    stack_type = st.stack_type.at[w, slot].set(
        jnp.where(do_push, push_type, st.stack_type[w, slot]))
    stack_mask = st.stack_mask.at[w, slot].set(
        jnp.where(do_push, push_mask, st.stack_mask[w, slot]))
    overflow_now = do_push & (sp_w >= cfg.warp_stack_depth)
    sp_new = sp_w + jnp.where(do_push, 1, 0)

    # ---- EXIT ------------------------------------------------------------
    is_exit = (op == isa.EXIT) & exec_this
    alive_new = jnp.where(is_exit, alive_w & ~exec_mask, alive_w)
    warp_done = is_exit & ~jnp.any(alive_new)
    # EXIT with survivors resumes a pending path from the stack
    exit_resume = is_exit & ~warp_done & (sp_new > 0)
    etop = jnp.maximum(sp_new - 1, 0)
    e_addr = stack_addr[w, etop]
    e_type = stack_type[w, etop]
    e_mask = _unpack(stack_mask[w, etop])
    sp_new = sp_new - jnp.where(exit_resume, 1, 0)
    active_new = jnp.where(
        exit_resume, e_mask & alive_new,
        jnp.where(diverge, ntk,
                  jnp.where(is_exit, alive_new, active_w)))

    # ---- next PC ----------------------------------------------------------
    resume_jump = exit_resume & (e_type == isa.STACK_TAKEN)
    pc_next = jnp.where(
        pop_taken, top_addr,
        jnp.where(uni_taken, imm,
                  jnp.where(resume_jump, e_addr, pc_w + 1)))
    # BAR: wait at the *next* instruction
    is_bar = (op == isa.BAR) & exec_this
    wstate_w = jnp.where(warp_done, FINISHED,
                         jnp.where(is_bar, WAIT, wstate[w]))

    # ---- counters / cycle cost -------------------------------------------
    is_gmem = (op == isa.LDG) | (op == isa.STG)
    is_smem = (op == isa.LDS) | (op == isa.STS)
    cost = jnp.where(
        exec_this,
        cfg.rows_per_warp
        + jnp.where(is_gmem, cfg.mem_latency_global, 0)
        + jnp.where(is_smem, cfg.mem_latency_shared, 0),
        1)                                   # a TAKEN pop costs one cycle
    c = st.counters
    op_c = jnp.where(exec_this, op, TRIP_SLOT)   # see state.TRIP_SLOT
    counters = Counters(
        op_issues=c.op_issues.at[op_c].add(1),
        op_lanes=c.op_lanes.at[op_c].add(
            jnp.sum(exec_mask).astype(jnp.int32)),
        cycles=c.cycles + cost,
        stack_ops=c.stack_ops + do_push.astype(jnp.int32)
        + do_pop.astype(jnp.int32) + exit_resume.astype(jnp.int32),
        max_sp=jnp.maximum(c.max_sp, sp_new),
        overflow=c.overflow | overflow_now.astype(jnp.int32))

    return SMState(
        pc=st.pc.at[w].set(pc_next),
        alive=st.alive.at[w].set(alive_new),
        active=st.active.at[w].set(active_new),
        wstate=wstate.at[w].set(wstate_w),
        stack_addr=stack_addr, stack_type=stack_type, stack_mask=stack_mask,
        sp=st.sp.at[w].set(sp_new),
        pred=pred, regs=regs, smem=smem, gmem=gmem, gw=gwrt,
        last_warp=w, counters=counters)
