"""Scalar-issue vs all-warp pipeline equivalence (hypothesis-free).

The contract of the lockstep all-warp pipeline: for every program the
paper's benchmarks can express — including divergent control flow and
barrier-heavy block cooperation — the final global memory, the written
mask, and every activity counter (per-opcode issues/lanes, cycles,
stack operations) are bit-identical to the seed one-warp-per-issue
interpreter kept as ``execute_backend="reference"``.

A seeded random-program sweep (mirroring the hypothesis strategies in
test_machine.py, but deterministic so it runs without the optional
dependency) additionally pins both issue disciplines to the pure-numpy
``RefMachine`` oracle.
"""
import numpy as np
import pytest

from repro.core import asm, customize, isa, machine, scheduler
from repro.core.machine import MachineConfig
from repro.core.microblaze import RefMachine
from repro.core.programs import ALL

VEC_BACKENDS = ("jnp",)

# divergent and barrier-heavy architectural variants (§4 axes)
CONFIGS = {
    "baseline": dict(),
    "sp32": dict(n_sp=32),
    "stack2": dict(warp_stack_depth=2),
}


def _counters_tuple(ctr):
    return (np.asarray(ctr.op_issues), np.asarray(ctr.op_lanes),
            int(ctr.cycles), int(ctr.stack_ops), int(ctr.max_sp),
            int(ctr.overflow))


def _run_block_all(code, bd, grid, gmem, cfg_kw):
    outs = {}
    for be in ("reference",) + VEC_BACKENDS:
        cfg = MachineConfig(execute_backend=be, **cfg_kw)
        gm, gw, ctr = machine.run_block(code, bd, (0, 0), grid, gmem, cfg)
        outs[be] = (np.asarray(gm), np.asarray(gw), _counters_tuple(ctr))
    return outs


def _assert_same(ref_out, vec_out, tag):
    np.testing.assert_array_equal(ref_out[0], vec_out[0],
                                  err_msg=f"{tag}: gmem")
    np.testing.assert_array_equal(ref_out[1], vec_out[1],
                                  err_msg=f"{tag}: written mask")
    names = ("op_issues", "op_lanes", "cycles", "stack_ops", "max_sp",
             "overflow")
    for a, b, what in zip(ref_out[2], vec_out[2], names):
        assert np.array_equal(a, b), f"{tag}: {what}: {a} vs {b}"


@pytest.mark.parametrize("cfg_name", sorted(CONFIGS))
@pytest.mark.parametrize("name", sorted(ALL))
def test_paper_program_block_equivalence(name, cfg_name, rng):
    """All five paper kernels, one block: bit-exact gmem + counters."""
    mod = ALL[name]
    n = 32
    code = mod.build(n)
    cfg_kw = dict(CONFIGS[cfg_name])
    if cfg_name == "stack2":
        # only valid for programs within the reduced stack bound
        prof = customize.analyze(code)
        if prof.required_stack_depth > 2:
            pytest.skip("program needs a deeper warp stack")
    g0 = mod.make_gmem(rng, n)
    grid, bd = mod.launch(n)
    outs = _run_block_all(code, bd, grid, g0, cfg_kw)
    for be in VEC_BACKENDS:
        _assert_same(outs["reference"], outs[be], f"{name}/{cfg_name}/{be}")


@pytest.mark.parametrize("name", sorted(ALL))
def test_paper_program_grid_equivalence(name, rng):
    """Full grid through the device-resident scheduler: final gmem and
    summed per-opcode issue/lane counters match the reference issue
    discipline exactly."""
    mod = ALL[name]
    n = 32
    code = mod.build(n)
    g0 = mod.make_gmem(rng, n)
    grid, bd = mod.launch(n)
    res = {}
    for be in ("reference",) + VEC_BACKENDS:
        cfg = MachineConfig(execute_backend=be)
        res[be] = scheduler.run_grid(code, grid, bd, g0.copy(), cfg)
    ref = res["reference"]
    for be in VEC_BACKENDS:
        vec = res[be]
        np.testing.assert_array_equal(ref.gmem, vec.gmem, err_msg=be)
        np.testing.assert_array_equal(ref.cycles_per_block,
                                      vec.cycles_per_block, err_msg=be)
        np.testing.assert_array_equal(ref.op_issues, vec.op_issues,
                                      err_msg=be)
        np.testing.assert_array_equal(ref.op_lanes, vec.op_lanes,
                                      err_msg=be)
        assert ref.stack_ops == vec.stack_ops, be
        assert ref.max_sp == vec.max_sp, be


# --------------------------------------------------------------------------
# seeded random programs vs the numpy RefMachine oracle
# --------------------------------------------------------------------------
_ALU_CHOICES = [isa.IADD, isa.ISUB, isa.IMUL, isa.IMIN, isa.IMAX, isa.AND,
                isa.OR, isa.XOR, isa.SHL, isa.SHR, isa.SAR, isa.IMAD]


def _random_straightline(rng):
    p = asm.Program("rand-straight")
    p.s2r("r0", isa.SR_TID)
    for _ in range(int(rng.integers(3, 15))):
        op = _ALU_CHOICES[int(rng.integers(len(_ALU_CHOICES)))]
        dst = int(rng.integers(1, 8))
        s1 = int(rng.integers(0, 8))
        if op == isa.IMAD:
            p.imad(dst, s1, int(rng.integers(0, 8)),
                   int(rng.integers(0, 8)))
        else:
            s2 = (int(rng.integers(-1000, 1000)) if rng.random() < 0.5
                  else int(rng.integers(0, 8)))
            p._alu(op, dst, s1, s2)
    for r in range(8):
        p.iadd("r8", "r0", 0)
        p.shl("r8", "r8", 3)
        p.iadd("r8", "r8", r)
        p.stg("r8", r)
    p.exit()
    return p.finish(pad_to=64)


def _random_branchy(rng):
    """Structured nested if/else on tid with proper SSY scoping, plus a
    barrier at the reconvergence point every other program (exercises
    WAIT/release interleaving under divergence)."""
    p = asm.Program("rand-branchy")
    p.s2r("r0", isa.SR_TID)
    p.mov("r1", 0)
    uid = [0]
    with_bar = rng.random() < 0.5

    def emit_block(depth):
        for _ in range(int(rng.integers(1, 4))):
            op = [isa.IADD, isa.IMUL, isa.XOR][int(rng.integers(3))]
            p._alu(op, 1, 1, int(rng.integers(1, 98)))
        if depth < 2 and rng.random() < 0.5:
            uid[0] += 1
            tag = uid[0]
            thr = int(rng.integers(0, 41))
            cond = ["LT", "GE", "EQ", "NE"][int(rng.integers(4))]
            p.ssy(f"join{tag}")
            p.isetp("p0", "r0", thr)
            p.guard("p0", cond).bra(f"taken{tag}")
            emit_block(depth + 1)          # not-taken path
            p.bra(f"join{tag}")
            p.label(f"taken{tag}")
            emit_block(depth + 1)          # taken path
            p.label(f"join{tag}", sync=True)
            p.nop()
            if with_bar and depth == 0:
                p.bar()

    emit_block(0)
    p.stg("r0", "r1", 0)
    p.exit()
    return p.finish(pad_to=96)


@pytest.mark.parametrize("backend", ("reference",) + VEC_BACKENDS)
def test_random_straightline_matches_refmachine(backend):
    for seed in range(6):
        rng = np.random.default_rng(seed)
        code = _random_straightline(rng)
        gmem = rng.integers(-1000, 1000, 40 * 8, dtype=np.int32)
        cfg = MachineConfig(execute_backend=backend)
        gm, gw, _ = machine.run_block(code, 40, (0, 0), (1, 1), gmem, cfg)
        ref = RefMachine(code, 40, (0, 0), (1, 1), gmem, cfg)
        ref.run()
        np.testing.assert_array_equal(np.asarray(gm), ref.gmem,
                                      err_msg=f"seed={seed}")
        np.testing.assert_array_equal(np.asarray(gw), ref.gw,
                                      err_msg=f"seed={seed}")


@pytest.mark.parametrize("backend", ("reference",) + VEC_BACKENDS)
def test_random_branchy_matches_refmachine(backend):
    for seed in range(6):
        rng = np.random.default_rng(seed + 100)
        code = _random_branchy(rng)
        gmem = np.zeros(64, np.int32)
        cfg = MachineConfig(execute_backend=backend)
        gm, _, ctr = machine.run_block(code, 64, (0, 0), (1, 1), gmem, cfg)
        ref = RefMachine(code, 64, (0, 0), (1, 1), gmem, cfg)
        ref.run()
        np.testing.assert_array_equal(np.asarray(gm), ref.gmem,
                                      err_msg=f"seed={seed}")
        assert int(ctr.max_sp) == ref.max_sp, f"seed={seed}"
        assert not bool(ctr.overflow)


def test_vectorized_barrier_smem_exchange():
    """Warps exchange data through shared memory across a barrier under
    the all-warp discipline (the lockstep analogue of the seed's
    interleaving test)."""
    p = asm.Program()
    p.s2r("r0", isa.SR_TID)
    p.sts("r0", "r0")            # smem[tid] = tid
    p.bar()
    p.mov("r2", 63)
    p.isub("r2", "r2", "r0")     # partner = 63 - tid
    p.lds("r3", "r2")
    p.stg("r0", "r3", 0)         # out[tid] = smem[63-tid]
    p.exit()
    code = p.finish(pad_to=16)
    for be in VEC_BACKENDS:
        out, _, _ = machine.run_block(
            code, 64, (0, 0), (1, 1), np.zeros(64, np.int32),
            MachineConfig(execute_backend=be))
        np.testing.assert_array_equal(np.asarray(out), 63 - np.arange(64))
