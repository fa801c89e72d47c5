"""Kernel compiler unit suite: DSL tracing, SSA IR invariants, the
pass pipeline, register allocation and the pinned ISSUE acceptance
(>= 15% emitted-instruction saving on at least one bundled kernel)."""
import numpy as np
import pytest

from repro import compiler
from repro.compiler import (CompileError, CompilerConfig, RegAllocError,
                            compile_kernel, compile_report)
from repro.compiler import dsl, ir, passes
from repro.compiler.kernels import COMPILED
from repro.core import isa, scheduler


def run1(code, grid, bd, gmem):
    return scheduler.run_grid(code, grid, bd, np.asarray(gmem, np.int32))


def ops_used(code) -> set:
    return {int(o) for o in code[:, isa.F_OP]}


# ------------------------------------------------------------- DSL / IR

def test_trace_verifies_and_prints():
    def k1(k):
        t = k.tid
        k.gmem[t + 32] = k.gmem[t] + 1
    fn = dsl.trace(k1)
    ir.verify(fn)
    text = str(fn)
    assert "ldg" in text and "stg" in text and "func @k1" in text


def test_variable_assigned_on_one_path_only_rejected():
    """A var first assigned inside a branch is uninitialized on the
    other path — the SSA construction rejects the read at the join."""
    def bad(k):
        with k.if_(k.tid < 4):
            w = k.var(5)
        k.gmem[0] = w
    with pytest.raises(CompileError, match="read before any assignment"):
        dsl.trace(bad)


def test_syncthreads_in_divergent_if_rejected():
    def bad(k):
        with k.if_(k.tid < 4):   # tid-dependent: divergent
            k.syncthreads()
    with pytest.raises(CompileError, match="deadlock the barrier"):
        dsl.trace(bad)


def test_syncthreads_in_uniform_if_allowed():
    def ok(k):
        with k.if_(k.blockIdx.x < 4):    # uniform per block
            k.syncthreads()
        k.gmem[k.tid] = 1
    dsl.trace(ok)


def test_for_with_divergent_bound_rejected():
    """A per-thread trip count is no longer rejected: it compiles to one
    SSY in the preheader, before the header, and a ``.S`` exit right
    after the header's test, and runs bit-exact.  What stays rejected
    is a per-thread step."""
    def div(k):
        with k.for_(0, k.tid) as i:      # per-thread trip count
            k.gmem[32 + k.tid] = i
    code = compile_kernel(div).code
    ops = code[:, isa.F_OP]
    ssy = np.flatnonzero(ops == isa.SSY)
    assert len(ssy) == 1
    target = int(code[ssy[0], isa.F_IMM])
    assert code[target, isa.F_FLAGS] & isa.FLAG_SYNC     # the .S exit
    test = target - 1                        # the header's @LT BRA body
    assert ops[test] == isa.BRA and code[test, isa.F_FLAGS] & isa.FLAG_GUARD
    assert ops[test - 1] == isa.ISETP and ssy[0] < test - 1
    res = scheduler.run_grid(code, (1, 1), (32, 1), np.zeros(64, np.int32))
    np.testing.assert_array_equal(res.gmem[32:],
                                  np.maximum(np.arange(32) - 1, 0))

    def bad(k):
        with k.for_(0, 64, k.tid + 1) as i:
            k.gmem[i] = 0
    with pytest.raises(CompileError, match="warp-uniform"):
        dsl.trace(bad)


def test_else_must_follow_if():
    def bad(k):
        k.gmem[0] = 1
        with k.else_():
            pass
    with pytest.raises(CompileError, match="immediately follow"):
        dsl.trace(bad)


def test_if_else_merges_values():
    def k1(k, n):
        t = k.tid
        v = k.var(0)
        with k.if_(t < n):
            v.set(t + 100)
        with k.else_():
            v.set(t - 100)
        k.gmem[64 + t] = v
    code = compile_kernel(k1, {"n": 7}).code
    res = run1(code, (1, 1), (32, 1), np.zeros(96))
    t = np.arange(32)
    want = np.where(t < 7, t + 100, t - 100)
    np.testing.assert_array_equal(res.gmem[64:96], want)


def test_cmp_materializes_in_arithmetic():
    def k1(k):
        t = k.tid
        k.gmem[32 + t] = (t > 4) + (t == 2) * 10
    code = compile_kernel(k1).code
    res = run1(code, (1, 1), (32, 1), np.zeros(64))
    t = np.arange(32)
    np.testing.assert_array_equal(res.gmem[32:],
                                  (t > 4).astype(int) + (t == 2) * 10)


def test_select_and_minmax():
    def k1(k):
        t = k.tid
        k.gmem[32 + t] = k.select(t < 10, k.min_(t, 5), k.max_(t, 20))
    code = compile_kernel(k1).code
    res = run1(code, (1, 1), (32, 1), np.zeros(64))
    t = np.arange(32)
    np.testing.assert_array_equal(
        res.gmem[32:], np.where(t < 10, np.minimum(t, 5),
                                np.maximum(t, 20)))


def test_pow2_division_and_modulo():
    def k1(k):
        t = k.tid
        k.gmem[32 + t] = (t // 8) * 100 + t % 8
    for optimize in (True, False):
        code = compile_kernel(k1, optimize=optimize).code
        res = run1(code, (1, 1), (32, 1), np.zeros(64))
        t = np.arange(32)
        np.testing.assert_array_equal(res.gmem[32:],
                                      (t // 8) * 100 + t % 8)


def test_non_pow2_division_rejected_at_emission():
    def bad(k):
        k.gmem[0] = k.tid // 3
    with pytest.raises(CompileError, match="power-of-two"):
        compile_kernel(bad)


def test_constant_division_by_zero_rejected():
    def bad(k):
        k.gmem[0] = (k.tid * 0 + 8) // 0
    with pytest.raises(CompileError):                  # fold path
        compile_kernel(bad)
    with pytest.raises(CompileError):                  # naive path
        compile_kernel(bad, optimize=False)


def test_for_non_positive_step_rejected():
    def zero_step(k):
        with k.for_(0, 10, 0) as i:
            k.gmem[i] = 0
    with pytest.raises(CompileError, match="step must be positive"):
        dsl.trace(zero_step)
    def down_step(k):
        with k.for_(10, 0, -1) as i:
            k.gmem[i] = 0
    with pytest.raises(CompileError, match="step must be positive"):
        dsl.trace(down_step)
    # a traced expression step that only FOLDS to zero is caught by the
    # pass pipeline (the tracer cannot see through the arithmetic)
    def folded_zero_step(k):
        with k.for_(0, 4, k.ntid - k.ntid) as i:
            k.gmem[i] = 0
    with pytest.raises(CompileError, match="folded to 0"):
        compile_kernel(folded_zero_step)


# ---------------------------------------------------------------- passes

def _scan_fn():
    return COMPILED["scan"].kernel, {"n": 32, "log2n": 5}


def test_constant_folding_removes_arithmetic():
    def k1(k):
        t = k.tid
        c = (t * 0 + 7) * 8 - 6           # folds to the constant 50
        k.gmem[t] = c
    ck = compile_kernel(k1)
    naive = compile_kernel(k1, optimize=False)
    # folded: one MOV #50 instead of a mul/add/mul/sub chain
    assert ck.n_instr < naive.n_instr
    res = run1(ck.code, (1, 1), (32, 1), np.zeros(64))
    np.testing.assert_array_equal(res.gmem[:32], 50)


def test_cse_merges_repeated_subexpressions():
    def k1(k):
        t = k.tid
        a = k.blockIdx.x * 64 + t
        b = k.blockIdx.x * 64 + t        # textual repeat
        k.gmem[a + 32] = k.gmem[b] + 1
    ck = compile_kernel(k1)
    naive = compile_kernel(k1, optimize=False)
    assert ck.n_instr < naive.n_instr


def test_strength_reduction_eliminates_multiplies():
    """histogram and scan become multiplier-free: *2^k -> SHL, so the
    customization analyzer can drop the multiplier (Table 6 style)."""
    from repro.core import customize
    for name in ("histogram", "scan"):
        code = COMPILED[name].build(64)
        used = ops_used(code)
        assert isa.IMUL not in used and isa.IMAD not in used, name
        assert not customize.minimal_config(code).enable_mul, name


def test_madfuse_emits_imad_for_spmv():
    code = COMPILED["spmv"].build(64)
    assert isa.IMAD in ops_used(code)
    naive = COMPILED["spmv"].build(64, optimize=False)
    assert isa.IMAD not in ops_used(naive)   # fusion is the pass's work


def test_ifconvert_removes_divergence_protocol():
    """The scan round's bounds-check if becomes SELP/predication: no
    SSY (and no warp-stack traffic) left in the optimized binary."""
    code = COMPILED["scan"].build(64)
    assert isa.SSY not in ops_used(code)
    naive = COMPILED["scan"].build(64, optimize=False)
    assert isa.SSY in ops_used(naive)


def test_ifconverted_scan_runs_with_zero_stack_depth():
    mod = COMPILED["scan"]
    code = mod.build(64)
    g0 = mod.make_gmem(np.random.default_rng(0), 64)
    res = run1(code, *mod.launch(64), g0.copy())
    assert res.max_sp == 0 and res.stack_ops == 0
    np.testing.assert_array_equal(res.gmem[mod.out_slice(64)],
                                  mod.oracle(g0, 64))


def test_unroll_respects_budget():
    def k1(k, n):
        acc = k.var(0)
        with k.for_(0, n) as i:
            acc.set(acc + k.gmem[i])
        k.gmem[n + k.tid] = acc
    small = compile_kernel(k1, {"n": 2})       # fits the unroll budget
    big = compile_kernel(k1, {"n": 32})        # does not
    assert isa.BRA not in ops_used(small.code)  # fully unrolled
    assert isa.BRA in ops_used(big.code)        # still a loop
    for ck, n in ((small, 2), (big, 32)):
        g = np.zeros(n + 32, np.int32)
        g[:n] = np.arange(n) + 1
        res = run1(ck.code, (1, 1), (32, 1), g)
        np.testing.assert_array_equal(res.gmem[n:n + 32],
                                      np.arange(n + 1)[-1] * (n + 1) // 2)


def test_dce_drops_unused_loads():
    def k1(k):
        t = k.tid
        dead = k.gmem[t + 7]              # never used
        del dead
        k.gmem[32 + t] = t
    ck = compile_kernel(k1)
    assert isa.LDG not in ops_used(ck.code)
    naive = compile_kernel(k1, optimize=False)
    assert isa.LDG in ops_used(naive.code)


def test_pass_log_is_monotone_recorded():
    ck = compile_kernel(*_scan_fn())
    names = [n for n, _ in ck.pass_log]
    assert names[0] == "trace"
    assert set(names[1:]) <= set(passes.PASSES)
    assert all(c > 0 for _, c in ck.pass_log)


def test_passes_preserve_semantics_seeded_kernels():
    """Differential: optimized and naive binaries agree on randomized
    inputs for a branchy/loopy kernel."""
    def k1(k, n):
        t = k.tid
        acc = k.var(0)
        with k.for_(0, n) as i:
            v = k.gmem[i * 4 % 64]
            with k.if_((v & 1) == 0):
                acc.set(acc + v * 3)
            with k.else_():
                acc.set(acc - (v >> 1))
        with k.if_(t < n):
            k.gmem[64 + t] = acc + t
    rep = compile_report(k1, {"n": 8})
    for seed in range(3):
        g0 = np.zeros(128, np.int32)
        g0[:64] = np.random.default_rng(seed).integers(-100, 100, 64)
        a = run1(rep.kernel.code, (1, 1), (32, 1), g0.copy())
        b = run1(rep.naive.code, (1, 1), (32, 1), g0.copy())
        np.testing.assert_array_equal(a.gmem, b.gmem)


# -------------------------------------------------------------- regalloc

def test_regalloc_spill_error_is_actionable():
    def hog(k):
        t = k.tid
        vals = [k.gmem[t + i] for i in range(20)]   # 20 live loads
        total = k.var(0)
        for v in vals:
            total.set(total + v)
        k.gmem[64 + t] = total
    # 20 simultaneously-live values cannot fit 16 GPRs... but the
    # tracer interleaves loads and adds, so force pressure by summing
    # in reverse order of loading
    def hog2(k):
        t = k.tid
        vals = [k.gmem[t + i] for i in range(20)]
        total = k.var(0)
        for v in reversed(vals):
            total.set(total + v)
        k.gmem[64 + t] = total
    with pytest.raises(RegAllocError, match="n_regs=16"):
        compile_kernel(hog2)


def test_regalloc_pred_pressure_error():
    def preds(k):
        t = k.tid
        cmps = [(t < i) for i in range(1, 7)]       # 6 live predicates
        acc = k.var(0)
        for c in reversed(cmps):
            acc.set(acc + c)
        k.gmem[32 + t] = acc
    with pytest.raises(RegAllocError, match="predicate registers"):
        compile_kernel(preds)


def test_small_register_file_config():
    def k1(k):
        t = k.tid
        k.gmem[32 + t] = k.gmem[t] + 1
    ck = compile_kernel(k1, config=CompilerConfig(n_regs=4))
    used = {int(r) for r in ck.code[:, isa.F_DST]}
    assert used <= {0, 1, 2, 3}
    res = run1(ck.code, (1, 1), (32, 1), np.zeros(64))
    np.testing.assert_array_equal(res.gmem[32:], 1)


def test_parallel_move_cycle_broken_with_xor_swaps():
    """Two loop-carried vars that swap every iteration force a cyclic
    parallel copy at the latch; the XOR rotation must preserve both."""
    def swap_k(k, n):
        a = k.var(1)
        b = k.var(1000)
        with k.for_(0, n) as i:
            tmp_a = a.get()
            a.set(b.get() + 0)    # +0 keeps the raw param flowing
            b.set(tmp_a + 1)
        t = k.tid
        k.gmem[t] = a
        k.gmem[32 + t] = b
    for n, (ea, eb) in ((0, (1, 1000)), (3, (1001, 1002)),
                        (4, (1002, 1002))):
        ck = compile_kernel(swap_k, {"n": n},
                            config=CompilerConfig(unroll_limit=0))
        res = run1(ck.code, (1, 1), (32, 1), np.zeros(64))
        a, b = 1, 1000
        for _ in range(n):
            a, b = b, a + 1
        np.testing.assert_array_equal(res.gmem[:32], a)
        np.testing.assert_array_equal(res.gmem[32:], b)


# ----------------------------------------------------- ISSUE acceptance

def test_acceptance_savings_at_least_15pct_histogram():
    """ISSUE acceptance: the pass pipeline reduces emitted instruction
    count by >= 15% vs passes-disabled emission on at least one bundled
    kernel — histogram clears it with margin."""
    rep = COMPILED["histogram"].report(64)
    assert rep.saving_pct >= 15.0, rep.saving_pct
    assert rep.kernel.n_instr < rep.naive.n_instr


def test_all_bundled_kernels_save_instructions():
    for name, mod in COMPILED.items():
        rep = mod.report(64)
        assert rep.saved_instrs > 0, name
        assert rep.kernel.n_instr <= 64, (name, "fits the 64 bucket")


def test_compile_is_fast():
    """The paper's pitch: under a second per kernel (ours: way under)."""
    import time
    t0 = time.perf_counter()
    for mod in COMPILED.values():
        mod.build(64)
    assert time.perf_counter() - t0 < 5.0


def test_gpgpu_compile_cli_all():
    from repro.launch import gpgpu_compile
    assert gpgpu_compile.main(["--all", "--no-ir"]) == 0


def test_gpgpu_compile_cli_single_with_ir(capsys):
    from repro.launch import gpgpu_compile
    assert gpgpu_compile.main(["histogram", "-n", "64"]) == 0
    out = capsys.readouterr().out
    assert "IR as traced" in out and "pass pipeline" in out
    assert "listing" in out and "optimized instructions" in out
