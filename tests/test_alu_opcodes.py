"""Every ALU opcode of the SP array, through the whole machine.

Each case is a short program run by ``machine.run_block`` under both
issue disciplines (the lockstep ``jnp`` step and the one-warp-per-issue
``reference`` interpreter): S2R, LDG of three operands, the opcode under
a guard that turns every fourth lane off, and an STG of each result.  An
80-thread block makes the last of its three warps partial.  The operands
pair ``INT_MIN``, ``INT_MAX``, 0, -1 and 1 with each other, and give
shift counts of 32 and above; ISETP is read back through ISET under
every condition.

Both numpy machines hold the result: ``RefMachine`` (gmem, the written
mask, cycles, the stack high-water mark, issues) and the benchmark's
interpreter ``bench/simt_ref.py`` (gmem and every counter field).
Neither shares ALU code with either discipline.  The §4.2 removals run
too: IMUL without the multiplier and IMAD without the third read port.
For the latter ``RefMachine`` computes ``a * b`` where the machine and
``simt_ref`` give 0, so its memory is held to ``simt_ref`` only.
"""
import importlib.util
from pathlib import Path

import numpy as np
import pytest

from repro.core import asm, isa, machine
from repro.core.machine import MachineConfig
from repro.core.microblaze import RefMachine

ROOT = Path(__file__).resolve().parents[1]


def _simt_ref():
    spec = importlib.util.spec_from_file_location(
        "simt_ref", ROOT / "bench" / "simt_ref.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SIMT_REF = _simt_ref()

DISCIPLINES = ("jnp", "reference")
T = 80                                  # threads: the third warp is partial
STRIDE = 96                             # words per gmem region
A, B, C, OUT = 0, STRIDE, 2 * STRIDE, 3 * STRIDE
N_SLOTS = 14                            # result regions after OUT
INT_MIN, INT_MAX = -2**31, 2**31 - 1
SENTINEL = 0x5A5A5A5A                   # what a guarded-off lane leaves
CONDS = sorted(isa.COND_IDS, key=isa.COND_IDS.get)
SHIFTS = (isa.SHL, isa.SHR, isa.SAR)
BINARY = (isa.IADD, isa.ISUB, isa.IMUL, isa.IMIN, isa.IMAX, isa.AND,
          isa.OR, isa.XOR) + SHIFTS
OPCODES = (isa.MOV, isa.IADD, isa.ISUB, isa.IMUL, isa.IMAD, isa.IMIN,
           isa.IMAX, isa.IABS, isa.AND, isa.OR, isa.XOR, isa.NOT, isa.SHL,
           isa.SHR, isa.SAR, isa.ISETP, isa.ISET, isa.SELP, isa.S2R)


def _operands(seed=0):
    """(a, b, c) for the T lanes: every pair of the special values, shift
    counts of 32 and above, equal pairs, then full-range randoms."""
    rng = np.random.default_rng(seed)

    def full(n):
        return rng.integers(INT_MIN, INT_MAX, n, endpoint=True)

    special = np.array([INT_MIN, INT_MAX, 0, -1, 1])
    a = np.concatenate([np.repeat(special, 5), full(T - 25)])
    b = np.concatenate([np.tile(special, 5),
                        [31, 32, 33, 63, 64, 95, -1, -31, -32, INT_MAX,
                         INT_MIN, 16, 100, 5, 37, 2**31 - 33],
                        a[41:51], full(T - 51)])
    c = np.concatenate([[INT_MIN, INT_MAX, -1, 0], full(T - 4)])
    return (x.astype(np.int32) for x in (a, b, c))


def _gmem():
    g = np.zeros(OUT + N_SLOTS * STRIDE, np.int32)
    a, b, c = _operands()
    g[A:A + T], g[B:B + T], g[C:C + T] = a, b, c
    return g


def _program(op) -> np.ndarray:
    """Load a, b, c into r1-r3; each result goes to its own ``slot`` of
    gmem and holds SENTINEL where the guard (p3: tid % 4 != 0) is off."""
    p = asm.Program(f"alu-{isa.OP_NAMES[op]}")
    p.s2r("r0", isa.SR_TID)
    for r, base in ((1, A), (2, B), (3, C)):
        p.ldg(f"r{r}", "r0", base)
    p.and_("r6", "r0", 3)
    p.isetp("p3", "r6", 0)
    slot = [0]

    def store():
        p.stg("r0", "r4", OUT + slot[0] * STRIDE)
        slot[0] += 1

    def guarded(emit):
        p.mov("r4", SENTINEL)
        p.guard("p3", "NE")
        emit()
        store()

    if op == isa.MOV:
        guarded(lambda: p.mov("r4", "r2"))
        guarded(lambda: p.mov("r4", INT_MIN))
    elif op in BINARY:
        imm = 33 if op in SHIFTS else INT_MIN
        guarded(lambda: p._alu(op, "r4", "r1", "r2"))
        guarded(lambda: p._alu(op, "r4", "r1", imm))
    elif op in (isa.NOT, isa.IABS):
        unary = p.not_ if op == isa.NOT else p.iabs
        guarded(lambda: unary("r4", "r1"))
        guarded(lambda: unary("r4", "r2"))
    elif op == isa.IMAD:
        guarded(lambda: p.imad("r4", "r1", "r2", "r3"))
    elif op == isa.ISETP:
        p.guard("p3", "NE").isetp("p0", "r1", "r2")
        for cond in CONDS:
            p.iset("r4", "p0", cond)
            store()
    elif op in (isa.ISET, isa.SELP):
        # the guard fields hold these opcodes' source predicate, so the
        # lanes go off by a divergent branch around them instead
        p.isetp("p0", "r1", "r2")
        conds = CONDS if op == isa.ISET else ("LT", "EQ", "HI", "F")
        dsts = range(4, 4 + len(conds))
        for r in dsts:
            p.mov(r, SENTINEL)
        p.ssy("join")
        p.guard("p3", "EQ").bra("join")
        for r, cond in zip(dsts, conds):
            if op == isa.ISET:
                p.iset(r, "p0", cond)
            else:
                p.selp(r, "r1", "r2", "p0", cond)
        p.label("join", sync=True)
        p.nop()
        for r in dsts:
            p.stg("r0", r, OUT + slot[0] * STRIDE)
            slot[0] += 1
    elif op == isa.S2R:
        for sel in (*range(11), 11, 99):
            guarded(lambda: p.s2r("r4", sel))
    assert 0 < slot[0] <= N_SLOTS
    p.exit()
    return p.finish()


def _assert_matches_oracles(op, cfg):
    code, g0 = _program(op), _gmem()
    gm, gw, ctr = machine.run_block(code, T, (0, 0), (1, 1), g0, cfg)
    gm, gw = np.asarray(gm), np.asarray(gw)

    ref = RefMachine(code, T, (0, 0), (1, 1), g0, cfg)
    ref.run()
    if cfg.num_read_operands >= 3 or op != isa.IMAD:
        # RefMachine reads a removed third port as 0 and keeps the
        # multiplier (IMAD = a * b); the machine and simt_ref remove the
        # whole mad datapath (IMAD = 0), so simt_ref alone holds that
        # case's memory
        np.testing.assert_array_equal(gm, ref.gmem,
                                      err_msg="RefMachine gmem")
    np.testing.assert_array_equal(gw, ref.gw, err_msg="RefMachine gw")
    assert int(ctr.cycles) == ref.cycles
    assert int(ctr.max_sp) == ref.max_sp
    assert int(np.sum(ctr.op_issues)) == ref.issues

    want = SIMT_REF.run_launch(
        code, (1, 1), (T, 1), g0, n_sp=cfg.n_sp, enable_mul=cfg.enable_mul,
        num_read_operands=cfg.num_read_operands)
    np.testing.assert_array_equal(gm, want["gmem"], err_msg="simt_ref gmem")
    np.testing.assert_array_equal(ctr.op_issues, want["op_issues"])
    np.testing.assert_array_equal(ctr.op_lanes, want["op_lanes"])
    assert [int(ctr.cycles)] == list(want["cycles_per_block"])
    assert int(ctr.stack_ops) == want["stack_ops"]
    assert int(ctr.max_sp) == want["max_sp"]
    assert bool(ctr.overflow) == want["overflow"]
    # the opcode ran on the guarded lanes only, and every lane stored
    assert int(ctr.op_lanes[op]) > 0
    assert gw[OUT:OUT + T].all() and not gw[OUT + T:OUT + STRIDE].any()
    return gm


@pytest.mark.parametrize("discipline", DISCIPLINES)
@pytest.mark.parametrize("op", OPCODES, ids=[isa.OP_NAMES[o]
                                             for o in OPCODES])
def test_alu_opcode_matches_numpy_machines(op, discipline):
    gm = _assert_matches_oracles(op, MachineConfig(
        execute_backend=discipline))
    out = gm[OUT:OUT + T]
    off = np.arange(T) % 4 == 0
    if op != isa.ISETP:
        assert (out[off] == SENTINEL).all()
    assert (out[~off] != SENTINEL).any()


@pytest.mark.parametrize("discipline", DISCIPLINES)
@pytest.mark.parametrize("op,removed", [
    (isa.IMUL, dict(enable_mul=False)),
    (isa.IMAD, dict(num_read_operands=2)),
], ids=["IMUL-no-multiplier", "IMAD-no-third-port"])
def test_removed_alu_unit_writes_zero(op, removed, discipline):
    """§4.2: with the multiplier or the third read port removed, the
    datapath is absent and the guarded lanes write 0."""
    gm = _assert_matches_oracles(op, MachineConfig(
        execute_backend=discipline, **removed))
    out = gm[OUT:OUT + T]
    on = np.arange(T) % 4 != 0
    assert (out[on] == 0).all()
