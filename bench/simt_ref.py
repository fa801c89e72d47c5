"""Plain reference of the simulated machine: a numpy interpreter.

It executes one kernel launch of the mini-ISA binary format (the
encoding is the interface tenants submit, restated below) and returns
the final global memory plus the per-launch counters the runtime
reports: per-opcode issues and active lanes, cycles per block, warp-
stack pushes/pops, the stack high-water mark and overflow.  It imports
nothing of the program under test.

Semantics (the architecture of arXiv:1606.06454 as the runtime models
it): warps of 32 lanes, a per-warp divergence stack (SSY pushes a
reconvergence entry, a divergent BRA pushes the taken path and runs the
not-taken path first, a ``.S`` instruction pops), 4-bit SZCO predicates
read through a condition LUT, block barriers, and a serialized-issue
cycle model: each executed warp-instruction costs ``32 / n_sp`` cycles
plus the memory latency of its class, a popped TAKEN entry costs one
cycle, and the block scheduler adds a fixed cost per block (counted by
the runtime per SM, not per block).

Blocks never communicate (true of every kernel served here), so they
run together: all warps of all blocks are contexts, and each step
executes the instruction at the lowest pending PC for every ready
context sitting there.  Global stores land in block order.  ``bits``
below 32 wraps every register result to that width: the lower-precision
control of the benchmark's check.
"""
from __future__ import annotations

import numpy as np

# ---- the binary format: opcodes, field slots, flags, stack entry types
(NOP, EXIT, MOV, IADD, ISUB, IMUL, IMAD, IMIN, IMAX, IABS, AND, OR, XOR,
 NOT, SHL, SHR, SAR, ISETP, ISET, SELP, S2R, LDG, STG, LDS, STS, BRA, SSY,
 BAR) = range(28)
NUM_OPCODES = 28
F_OP, F_DST, F_SRC1, F_SRC2, F_SRC3, F_IMM, F_FLAGS, F_GPRED, F_GCOND, \
    F_PDST = range(10)
FLAG_SRC2_IMM, FLAG_SYNC, FLAG_GUARD, FLAG_SRC1_IMM = 1, 2, 4, 8
STACK_RECONV, STACK_TAKEN = 0, 1
WARP = 32
WRITES_REG = frozenset((MOV, IADD, ISUB, IMUL, IMAD, IMIN, IMAX, IABS, AND,
                        OR, XOR, NOT, SHL, SHR, SAR, ISET, SELP, S2R, LDG,
                        LDS))
READY, WAIT, FINISHED = 0, 1, 2


def _cond_lut() -> np.ndarray:
    """[condition, SZCO nibble] -> lane mask bit (S=1, Z=2, C=4, O=8)."""
    lut = np.ones((16, 16), bool)
    for f in range(16):
        s, z, c, o = (bool(f & b) for b in (1, 2, 4, 8))
        lt = s ^ o
        lut[:12, f] = (False, lt, z, lt or z, not (lt or z), not z,
                       not lt, True, c, c or z, not (c or z), not c)
    return lut


COND_LUT = _cond_lut()


def _wrap(x: np.ndarray, bits: int) -> np.ndarray:
    x = x.astype(np.int32)
    if bits >= 32:
        return x
    half = 1 << (bits - 1)
    return (((x.astype(np.int64) + half) & ((1 << bits) - 1)) - half) \
        .astype(np.int32)


def run_launch(code, grid, block_dim, gmem, *, n_sp: int = 8,
               n_regs: int = 16, warp_stack_depth: int = 32,
               enable_mul: bool = True, num_read_operands: int = 3,
               smem_words: int = 4096, mem_latency_global: int = 8,
               mem_latency_shared: int = 2, max_cycles: int = 4_000_000,
               bits: int = 32) -> dict:
    """Run one launch; returns ``gmem`` and the launch's counters."""
    code = np.asarray(code, np.int64)
    gx, gy = grid
    bdx, bdy = block_dim
    nthreads = bdx * bdy
    B = gx * gy
    W = -(-nthreads // WARP)
    C = B * W
    D, R, S = warp_stack_depth, n_regs, smem_words
    rows = max(1, WARP // n_sp)
    gm = np.asarray(gmem, np.int32).copy()
    G = gm.shape[0]

    blk = np.repeat(np.arange(B), W)                 # context -> block
    wid = np.tile(np.arange(W), B)                   # context -> warp
    lanes = np.arange(WARP)
    tid = wid[:, None] * WARP + lanes[None, :]       # (C, 32) flat tid
    exists = tid < nthreads
    bx, by = blk % gx, blk // gx

    regs = np.zeros((C, WARP, R), np.int32)
    pred = np.zeros((C, WARP, 4), np.int64)
    alive = exists.copy()
    active = exists.copy()
    pc = np.zeros(C, np.int64)
    sp = np.zeros(C, np.int64)
    wstate = np.where(exists.any(1), READY, FINISHED)
    st_addr = np.zeros((C, D), np.int64)
    st_type = np.zeros((C, D), np.int64)
    st_mask = np.zeros((C, D, WARP), bool)
    smem = np.zeros((B, S), np.int32)

    op_issues = np.zeros((B, NUM_OPCODES), np.int64)
    op_lanes = np.zeros((B, NUM_OPCODES), np.int64)
    cycles = np.zeros(B, np.int64)
    stack_ops = np.zeros(B, np.int64)
    max_sp = np.zeros(B, np.int64)
    overflow = np.zeros(B, bool)

    while True:
        live = (wstate != FINISHED) & (cycles[blk] < max_cycles)
        if not live.any():
            break
        ready = live & (wstate == READY)
        # barrier release: a block with no ready warp wakes its waiters
        n_ready = np.bincount(blk[ready], minlength=B)
        wake = live & (wstate == WAIT) & (n_ready[blk] == 0)
        if wake.any():
            wstate[wake] = READY
            ready = ready | wake
        p = int(pc[ready].min())
        K = np.nonzero(ready & (pc == p))[0]
        n = len(K)
        bK = blk[K]
        ins = code[p]
        op, dst, r1, r2, r3, imm = (int(v) for v in ins[:6])
        imm = int(np.int32(np.uint32(imm & 0xFFFFFFFF)))
        flags, gp, gc, pd = (int(v) for v in ins[6:10])

        spK = sp[K].copy()
        act = active[K]
        pop_taken = np.zeros(n, bool)
        do_pop = np.zeros(n, bool)
        top_addr = np.zeros(n, np.int64)
        if flags & FLAG_SYNC:
            do_pop = spK > 0
            top = np.maximum(spK - 1, 0)
            top_addr = st_addr[K, top]
            pop_taken = do_pop & (st_type[K, top] == STACK_TAKEN)
            act = np.where(do_pop[:, None], st_mask[K, top], act)
            spK = spK - do_pop
        exec_this = ~pop_taken
        cond = COND_LUT[gc, pred[K, :, gp]]                     # (n, 32)
        al = alive[K]
        guard = cond if flags & FLAG_GUARD else True
        emask = act & al & guard & exec_this[:, None]

        s1 = np.full((n, WARP), imm, np.int32) if flags & FLAG_SRC1_IMM \
            else regs[K, :, r1]
        s2 = np.full((n, WARP), imm, np.int32) if flags & FLAG_SRC2_IMM \
            else regs[K, :, r2]
        s3 = regs[K, :, r3] if num_read_operands >= 3 \
            else np.zeros((n, WARP), np.int32)
        addr = s1.astype(np.int64) + imm

        res = None
        with np.errstate(over="ignore"):
            if op == MOV:
                res = s2
            elif op == IADD:
                res = s1 + s2
            elif op == ISUB:
                res = s1 - s2
            elif op == IMUL:
                res = s1 * s2 if enable_mul else np.zeros_like(s1)
            elif op == IMAD:
                res = s1 * s2 + s3 if enable_mul and num_read_operands >= 3 \
                    else np.zeros_like(s1)
            elif op == IMIN:
                res = np.minimum(s1, s2)
            elif op == IMAX:
                res = np.maximum(s1, s2)
            elif op == IABS:
                res = np.abs(s1)
            elif op == AND:
                res = s1 & s2
            elif op == OR:
                res = s1 | s2
            elif op == XOR:
                res = s1 ^ s2
            elif op == NOT:
                res = ~s1
            elif op in (SHL, SHR):
                sh = (s2 & 31).astype(np.uint32)
                u = s1.view(np.uint32)
                res = ((u << sh) if op == SHL else (u >> sh)).view(np.int32)
            elif op == SAR:
                res = s1 >> (s2 & 31)
            elif op == ISET:
                res = cond.astype(np.int32)
            elif op == SELP:
                res = np.where(cond, s1, s2)
            elif op == S2R:
                t = tid[K]
                sel = min(max(imm, 0), 10)
                vals = (t % bdx, t // bdx, bx[K], by[K], bdx, bdy, gx, gy, t,
                        by[K] * gx + bx[K], nthreads)
                v = vals[sel]
                res = np.broadcast_to(
                    v[:, None] if np.ndim(v) == 1 else v,
                    (n, WARP)).astype(np.int32)
            elif op == LDG:
                res = gm[np.clip(addr, 0, G - 1)]
            elif op == LDS:
                res = smem[bK[:, None], np.clip(addr, 0, S - 1)]

            if op in WRITES_REG and res is not None:
                regs[K, :, dst] = np.where(emask, _wrap(res, bits),
                                           regs[K, :, dst])
            elif op == ISETP:
                d = s1 - s2
                nib = ((d < 0).astype(np.int64)
                       | ((d == 0).astype(np.int64) << 1)
                       | ((s1.view(np.uint32) < s2.view(np.uint32))
                          .astype(np.int64) << 2)
                       | ((((s1 ^ s2) & (s1 ^ d)) < 0).astype(np.int64) << 3))
                pred[K, :, pd] = np.where(emask, nib, pred[K, :, pd])
            elif op == STG:
                a = np.clip(addr, 0, G - 1)[emask]
                gm[a] = s2[emask]
            elif op == STS:
                rows_b = np.broadcast_to(bK[:, None], emask.shape)[emask]
                smem[rows_b, np.clip(addr, 0, S - 1)[emask]] = s2[emask]

        # ---- control: divergence stack, EXIT, next PC, barrier -------
        part = act & al & exec_this[:, None]
        taken = part & cond if flags & FLAG_GUARD else part
        diverge = np.zeros(n, bool)
        uni = np.zeros(n, bool)
        ntk = part & ~taken
        if op == BRA:
            any_t, any_n = taken.any(1), ntk.any(1)
            diverge = exec_this & any_t & any_n
            uni = exec_this & any_t & ~any_n
        is_ssy = exec_this & (op == SSY)
        do_push = diverge | is_ssy
        overflow_now = do_push & (spK >= D)
        if do_push.any():
            slot = np.clip(spK, 0, D - 1)
            kk, ss = K[do_push], slot[do_push]
            st_addr[kk, ss] = imm
            st_type[kk, ss] = STACK_RECONV if op == SSY else STACK_TAKEN
            st_mask[kk, ss] = (part if op == SSY else taken)[do_push]
        sp_new = spK + do_push
        is_exit = exec_this & (op == EXIT)
        alive_new = np.where(is_exit[:, None], al & ~emask, al)
        done = is_exit & ~alive_new.any(1)
        exit_resume = is_exit & ~done & (sp_new > 0)
        etop = np.maximum(sp_new - 1, 0)
        e_addr, e_type, e_mask = st_addr[K, etop], st_type[K, etop], \
            st_mask[K, etop]
        sp_new = sp_new - exit_resume
        active[K] = np.where(
            exit_resume[:, None], e_mask & alive_new,
            np.where(diverge[:, None], ntk,
                     np.where(is_exit[:, None], alive_new, act)))
        alive[K] = alive_new
        pc[K] = np.where(pop_taken, top_addr,
                         np.where(uni, imm,
                                  np.where(exit_resume & (e_type == STACK_TAKEN),
                                           e_addr, p + 1)))
        sp[K] = sp_new
        if op == BAR:
            wstate[K[exec_this]] = WAIT
        wstate[K[done]] = FINISHED

        mem_lat = mem_latency_global if op in (LDG, STG) else \
            mem_latency_shared if op in (LDS, STS) else 0
        cost = np.where(exec_this, rows + mem_lat, 1)
        np.add.at(cycles, bK, cost)
        np.add.at(op_issues[:, op], bK, exec_this.astype(np.int64))
        np.add.at(op_lanes[:, op], bK, emask.sum(1))
        np.add.at(stack_ops, bK, do_push.astype(np.int64) + do_pop
                  + exit_resume)
        np.maximum.at(max_sp, bK, sp_new)
        np.logical_or.at(overflow, bK, overflow_now)

    return {"gmem": gm, "cycles_per_block": cycles,
            "op_issues": op_issues.sum(0), "op_lanes": op_lanes.sum(0),
            "stack_ops": int(stack_ops.sum()), "max_sp": int(max_sp.max()),
            "overflow": bool(overflow.any())}
