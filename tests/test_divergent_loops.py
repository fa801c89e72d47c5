"""Divergent loops: ``for_`` bounds that differ by lane.

Each case compiles a DSL kernel whose trip counts come from memory, with
and without the pass pipeline, and runs it through three machines: the
sequential one-warp-per-issue reference (``execute_backend=
"reference"``), the all-warp ``jnp`` step, and the benchmark's plain
numpy interpreter (``bench/simt_ref.py``, which imports nothing of the
program).  Every run must write what numpy computes, and the three
must agree on the whole memory and every counter.  The warp stack holds
one entry per open loop, so its high-water mark does not grow with the
trip count.
"""
import importlib.util
from pathlib import Path

import numpy as np
import pytest

from repro.compiler import CompileError, compile_kernel, dsl, passes
from repro.core import isa, scheduler
from repro.core.machine import MachineConfig

ROOT = Path(__file__).resolve().parents[1]
T = 64                                   # threads: one block of two warps
LO, HI, OUT, AUX = 0, T, 2 * T, 3 * T    # gmem regions, T words each


def _simt_ref():
    spec = importlib.util.spec_from_file_location(
        "simt_ref", ROOT / "bench" / "simt_ref.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SIMT_REF = _simt_ref()


# ------------------------------------------------------------ the kernels
def k_sum(k):
    """acc = sum of 3j + 1 over j in [lo, hi)."""
    t = k.tid
    acc = k.var(0)
    with k.for_(k.gmem[LO + t], k.gmem[HI + t]) as j:
        acc.set(acc + j * 3 + 1)
    k.gmem[OUT + t] = acc


def k_if_inside(k):
    """A divergent if in the body, storing on one arm."""
    t = k.tid
    acc = k.var(0)
    with k.for_(k.gmem[LO + t], k.gmem[HI + t]) as j:
        with k.if_(((j + t) & 3) == 0):
            acc.set(acc + j)
            k.gmem[AUX + t] = j
        with k.else_():
            acc.set(acc - 1)
    k.gmem[OUT + t] = acc


def k_inside_if(k):
    """The loop inside a divergent if; odd lanes skip it."""
    t = k.tid
    acc = k.var(7)
    with k.if_((t & 1) == 0):
        with k.for_(k.gmem[LO + t], k.gmem[HI + t]) as j:
            acc.set(acc + j)
    k.gmem[OUT + t] = acc


def k_nested(k):
    """Nested divergent loops: the inner trip count is j % 4 + lane % 4."""
    t = k.tid
    acc = k.var(0)
    with k.for_(k.gmem[LO + t], k.gmem[HI + t]) as j:
        with k.for_(0, (j & 3) + t % 4) as i:
            acc.set(acc + i + j)
    k.gmem[OUT + t] = acc


def _np_sum(lo, hi, tid):
    return np.array([sum(3 * j + 1 for j in range(a, b))
                     for a, b in zip(lo, hi)])


def _np_if_inside(lo, hi, tid):
    return np.array([sum(j if (j + t) % 4 == 0 else -1 for j in range(a, b))
                     for a, b, t in zip(lo, hi, tid)])


def _np_inside_if(lo, hi, tid):
    return np.array([7 + (sum(range(a, b)) if t % 2 == 0 else 0)
                     for a, b, t in zip(lo, hi, tid)])


def _np_nested(lo, hi, tid):
    return np.array([sum(i + j for j in range(a, b)
                         for i in range(j % 4 + t % 4))
                     for a, b, t in zip(lo, hi, tid)])


KERNELS = {"sum": (k_sum, _np_sum), "if_inside": (k_if_inside, _np_if_inside),
           "inside_if": (k_inside_if, _np_inside_if),
           "nested": (k_nested, _np_nested)}

# trip counts by lane: (lo, hi) of every thread
_tid = np.arange(T)
TRIPS = {
    "zero": (_tid * 5, _tid * 5),
    "equal": (_tid, _tid + 9),
    "ramp": (_tid % 7, _tid % 7 + (_tid % 32) * 100 // 31),     # 0..100
}
CASES = [("sum", "zero"), ("sum", "equal"), ("sum", "ramp"),
         ("if_inside", "ramp"), ("inside_if", "ramp"), ("nested", "equal"),
         ("nested", "ramp")]


def _gmem(trips):
    lo, hi = TRIPS[trips]
    g = np.zeros(4 * T, np.int32)
    g[LO:LO + T], g[HI:HI + T] = lo, hi
    return g


def _counters(res) -> dict:
    get = res.get if isinstance(res, dict) else \
        (lambda f: getattr(res, f))
    return {f: np.asarray(get(f), np.int64) for f in
            ("gmem", "op_issues", "op_lanes", "cycles_per_block",
             "stack_ops", "max_sp", "overflow")}


@pytest.mark.parametrize("optimize", (True, False), ids=("opt", "naive"))
@pytest.mark.parametrize("kernel,trips", CASES,
                         ids=[f"{k}-{t}" for k, t in CASES])
def test_divergent_loop_machines_agree(kernel, trips, optimize):
    fn, want_fn = KERNELS[kernel]
    code = compile_kernel(fn, optimize=optimize).code
    g0 = _gmem(trips)
    want = want_fn(*TRIPS[trips], _tid)
    runs = {be: _counters(scheduler.run_grid(
        code, (1, 1), (T, 1), g0.copy(), MachineConfig(execute_backend=be)))
        for be in ("reference", "jnp")}
    runs["simt_ref"] = _counters(SIMT_REF.run_launch(code, (1, 1), (T, 1),
                                                     g0.copy()))
    for name, got in runs.items():
        np.testing.assert_array_equal(got["gmem"][OUT:OUT + T], want,
                                      err_msg=name)
        for f, v in got.items():
            np.testing.assert_array_equal(v, runs["reference"][f],
                                          err_msg=f"{name} {f}")
    ref = runs["reference"]
    assert int(ref["overflow"]) == 0
    # one entry per open loop plus the TAKEN entry a trip pushes and
    # its exit pops at once, whatever the trip count; an if adds its
    # reconvergence entry and, while one arm runs, the other arm's
    depth = {"sum": 2, "if_inside": 3, "inside_if": 4, "nested": 3}
    assert int(ref["max_sp"]) <= depth[kernel]
    if trips == "ramp":
        # lanes leave on up to 100 different trips: far past the stack
        assert int(TRIPS["ramp"][1].max() - TRIPS["ramp"][0].min()) > \
            MachineConfig().warp_stack_depth


def test_syncthreads_in_divergent_loop_rejected():
    def bad(k):
        with k.for_(0, k.gmem[k.tid]):
            k.syncthreads()
    with pytest.raises(CompileError, match="deadlock the barrier"):
        dsl.trace(bad)


def test_syncthreads_after_divergent_loop_allowed():
    def ok(k):
        acc = k.var(0)
        with k.for_(0, k.gmem[k.tid]) as j:
            acc.set(acc + j)
        k.syncthreads()
        k.gmem[k.tid] = acc
    dsl.trace(ok)


def test_values_written_in_divergent_loop_are_not_uniform():
    """A var carried around a divergent loop, read before its write in
    the body, is non-uniform there: an if on it keeps its own SSY."""
    def kern(k):
        acc = k.var(0)
        with k.for_(0, k.gmem[k.tid]) as j:
            with k.if_(acc > 3):
                k.gmem[64 + k.tid] = j
            acc.set(acc + 1)
        k.gmem[32 + k.tid] = acc
    ck = compile_kernel(kern, optimize=False)
    assert int(np.sum(ck.code[:, isa.F_OP] == isa.SSY)) == 2
    g = np.zeros(96, np.int32)
    g[:32] = np.arange(32) % 9
    res = scheduler.run_grid(ck.code, (1, 1), (32, 1), g.copy())
    np.testing.assert_array_equal(res.gmem[32:64], g[:32])
    np.testing.assert_array_equal(res.gmem[64:96],
                                  np.where(g[:32] > 4, g[:32] - 1, 0))


@pytest.mark.parametrize("name", passes.DEFAULT_PASSES)
def test_passes_keep_divergent_loop_top_tested(name):
    """No pass unrolls, if-converts or rotates a divergent loop: after
    each, its header still holds the trip test and branches to the body
    or to its exit, and the loop is still recorded as divergent."""
    fn = dsl.trace(k_if_inside)
    passes.PASSES[name](fn)
    passes.check_loop_steps(fn)
    loops = [lp for lp in fn.loops if lp.divergent]
    assert len(loops) == 1
    lp = loops[0]
    t = lp.header.term
    assert t.t in fn.blocks and t.f is lp.exit and t.reconv is lp.exit
    assert [i.op for i in lp.header.instrs] == ["icmp"]
    assert fn.blocks.index(lp.exit) == fn.blocks.index(lp.header) + 1


def test_uniform_loops_keep_their_layout():
    """Uniform bounds compile as before: no warp-stack protocol, the
    header's test first and its exit branch forward."""
    from repro.compiler.kernels import COMPILED
    code = COMPILED["spmv"].build(64)
    assert isa.SSY not in code[:, isa.F_OP]
    assert not np.any(code[:, isa.F_FLAGS] & isa.FLAG_SYNC)
    bra = np.flatnonzero(code[:, isa.F_OP] == isa.BRA)
    guarded = [b for b in bra if code[b, isa.F_FLAGS] & isa.FLAG_GUARD]
    assert len(guarded) == 1 and code[guarded[0], isa.F_IMM] > guarded[0]
