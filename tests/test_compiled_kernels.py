"""The four DSL-compiled kernels (histogram, scan, ELL and CSR SpMV):
bit-exact vs numpy oracles across grid/block sizes through run_grid,
and differentially through the RuntimeServer under every drain policy,
mixed with the legacy five benchmarks."""
import numpy as np
import pytest

from repro import runtime as rt
from repro.compiler.kernels import COMPILED, histogram, spmv_csr
from repro.core import scheduler
from repro.core.machine import MachineConfig
from repro.core.programs import ALL, compiled_kernels
from repro.runtime import registry as reg

POLICY_NAMES = ("monolithic", "bucket", "fair", "balanced")

#: sizes exercising 1, 2 and 4+ blocks where the kernel supports them
SIZES = {"histogram": (32, 64, 128, 256), "scan": (32, 64, 128, 256),
         "spmv": (32, 64, 128), "spmv_csr": (128, 256, 512)}


def _seq(name, n, gseed=0):
    mod = COMPILED[name]
    code = mod.build(n)
    g0 = mod.make_gmem(np.random.default_rng(gseed), n)
    res = scheduler.run_grid(code, *mod.launch(n), g0.copy())
    return mod, code, g0, res


def _assert_bit_identical(got, want):
    np.testing.assert_array_equal(got.gmem, want.gmem)
    np.testing.assert_array_equal(got.cycles_per_block,
                                  want.cycles_per_block)
    np.testing.assert_array_equal(got.op_issues, want.op_issues)
    np.testing.assert_array_equal(got.op_lanes, want.op_lanes)


# ------------------------------------------------------ run_grid oracles

@pytest.mark.parametrize("name", sorted(COMPILED))
def test_compiled_kernel_matches_oracle_across_sizes(name):
    mod = COMPILED[name]
    for n in SIZES[name]:
        for gseed in (0, 1):
            code = mod.build(n)
            g0 = mod.make_gmem(np.random.default_rng(gseed), n)
            res = scheduler.run_grid(code, *mod.launch(n), g0.copy())
            np.testing.assert_array_equal(
                res.gmem[mod.out_slice(n)], mod.oracle(g0, n),
                err_msg=f"{name} n={n} seed={gseed}")


@pytest.mark.parametrize("name", sorted(COMPILED))
def test_compiled_kernel_reference_bit_exact(name):
    """The lockstep ``jnp`` step reproduces the one-warp-per-issue
    ``reference`` interpreter bit-for-bit on every DSL-compiled kernel
    through ``run_grid`` (gmem, per-block cycles, per-opcode issue/lane
    counters)."""
    mod = COMPILED[name]
    n = SIZES[name][1]
    code = mod.build(n)
    g0 = mod.make_gmem(np.random.default_rng(5), n)
    res = {}
    for be in ("jnp", "reference"):
        cfg = MachineConfig(execute_backend=be)
        res[be] = scheduler.run_grid(code, *mod.launch(n), g0.copy(), cfg)
    _assert_bit_identical(res["jnp"], res["reference"])
    assert res["jnp"].stack_ops == res["reference"].stack_ops
    assert res["jnp"].max_sp == res["reference"].max_sp


@pytest.mark.parametrize("name", sorted(COMPILED))
def test_naive_and_optimized_binaries_agree(name):
    """Passes change instructions, never results: the passes-disabled
    binary produces identical global memory."""
    mod = COMPILED[name]
    n = SIZES[name][1]
    g0 = mod.make_gmem(np.random.default_rng(3), n)
    opt = scheduler.run_grid(mod.build(n), *mod.launch(n), g0.copy())
    naive = scheduler.run_grid(mod.build(n, optimize=False),
                               *mod.launch(n), g0.copy())
    np.testing.assert_array_equal(opt.gmem, naive.gmem)


def test_histogram_two_pass_reduce():
    """Multi-block histogram: per-block partials then the reduce pass
    recover the full-input histogram (the '+ reduce' of the ISSUE)."""
    for n in (128, 256):
        g0 = histogram.make_gmem(np.random.default_rng(9), n)
        gm, results = histogram.run_passes(
            scheduler.run_grid, histogram.build(n), n, g0.copy())
        assert len(results) == 2
        np.testing.assert_array_equal(gm[histogram.final_slice(n)],
                                      histogram.final_oracle(g0, n))
        # pass 1's partials are what the single-launch oracle predicts
        np.testing.assert_array_equal(
            results[0].gmem[histogram.out_slice(n)],
            histogram.oracle(g0, n))


def test_multiblock_kernels_scale_to_two_sms():
    """spmv at n=128 runs 4 blocks: a second SM must shorten the
    critical path (the Table 3 scaling property, on a compiled
    kernel)."""
    mod = COMPILED["spmv"]
    n = 128
    code = mod.build(n)
    g0 = mod.make_gmem(np.random.default_rng(0), n)
    res = scheduler.run_grid(code, *mod.launch(n), g0.copy())
    assert res.sm_cycles(1) > res.sm_cycles(2)


def test_compiled_kernels_land_in_small_code_bucket():
    """Unpadded compiled binaries bucket at 64 instructions — a
    different footprint axis than the hand-written five (96), so mixed
    workloads really exercise heterogeneous code buckets."""
    regy = rt.ModuleRegistry()
    for name, mod in COMPILED.items():
        m = regy.load(mod.build(64), name)
        assert m.padded_len == 64, (name, m.padded_len)
    legacy = regy.load(ALL["bitonic"].build(32), "bitonic")
    assert legacy.padded_len == 96


def test_programs_compiled_kernels_accessor():
    ck = compiled_kernels()
    assert sorted(ck) == ["histogram", "scan", "spmv", "spmv_csr"]
    for mod in ck.values():
        for attr in ("build", "launch", "make_gmem", "oracle",
                     "out_slice", "n_threads"):
            assert hasattr(mod, attr)


def _csr_forced(n: int, rng) -> np.ndarray:
    """spmv_csr's memory with an empty row, a row of 101 nonzeros and
    one of up to 120 (in two warps of the first block), and the rest at
    random."""
    nnz = spmv_csr.nnz_of(n)
    counts = np.zeros(n, np.int64)
    counts[[3, 40]] = (101, min(120, nnz - 101))
    rest = np.setdiff1d(np.arange(n), [0, 3, 40])
    counts[rest] = np.bincount(rng.choice(rest, nnz - counts.sum()),
                               minlength=n)[rest]
    cols = np.concatenate([np.sort(rng.choice(n, c, replace=False))
                           for c in counts])
    row_ptr = np.concatenate([[0], np.cumsum(counts)])
    return spmv_csr.pack(n, row_ptr, cols,
                         rng.integers(-100, 100, nnz, dtype=np.int32),
                         rng.integers(-100, 100, n, dtype=np.int32))


@pytest.mark.parametrize("n", SIZES["spmv_csr"])
def test_spmv_csr_through_server_with_forced_rows(n):
    """CSR SpMV through the normal path (submit -> drain -> execute),
    with an empty row and rows of 100+ nonzeros: bit-exact against the
    numpy oracle, and in memory and every counter against the
    sequential reference machine, with the warp stack at most two deep
    however long a row is."""
    rng = np.random.default_rng(n)
    g0 = _csr_forced(n, rng)
    code = spmv_csr.build(n)
    srv = rt.RuntimeServer(n_sm=2, policy="bucket", resident_gmem=True)
    fut = srv.submit_future(code, *spmv_csr.launch(n), g0.copy(),
                            client="csr")
    srv.drain()
    got = fut.result()
    np.testing.assert_array_equal(
        np.asarray(got.gmem)[spmv_csr.out_slice(n)], spmv_csr.oracle(g0, n))
    ref = scheduler.run_grid(code, *spmv_csr.launch(n), g0.copy(),
                             MachineConfig(execute_backend="reference"))
    _assert_bit_identical(got._replace(gmem=np.asarray(got.gmem)), ref)
    assert got.stack_ops == ref.stack_ops and got.max_sp == ref.max_sp
    assert got.max_sp <= 2 and not got.overflow


# ------------------------------------------- server differential suite

@pytest.mark.parametrize("policy", POLICY_NAMES)
def test_server_differential_compiled_with_legacy(policy):
    """ISSUE acceptance: every compiled kernel drains bit-exact through
    the RuntimeServer under every policy, in a window shared with
    legacy-five tenants."""
    srv = rt.RuntimeServer(n_sm=2, policy=policy)
    want = {}
    # compiled tenants
    for i, name in enumerate(sorted(COMPILED)):
        n = SIZES[name][0]
        mod, code, g0, seq = _seq(name, n, gseed=i)
        t = srv.submit(code, *mod.launch(n), g0.copy(),
                       client=f"compiled{i}")
        want[t] = seq
    # legacy window-mates
    for j, (lname, ln) in enumerate((("bitonic", 32), ("autocorr", 32))):
        lmod = ALL[lname]
        lcode = lmod.build(ln)
        lg0 = lmod.make_gmem(np.random.default_rng(40 + j), ln)
        seq = scheduler.run_grid(lcode, *lmod.launch(ln), lg0.copy())
        t = srv.submit(lcode, *lmod.launch(ln), lg0.copy(),
                       client="legacy")
        want[t] = seq
    results, stats = srv.drain()
    assert sorted(results) == sorted(want)
    for t, seq in want.items():
        _assert_bit_identical(results[t], seq)
    assert stats.n_launches == len(want)


def test_server_mixed_workload_all_policies_agree():
    """The serving CLI's mixed workload (legacy + compiled) drains to
    identical per-ticket memories under every policy."""
    from repro.launch.gpgpu_serve import build_workload
    work = build_workload(8, seed=5)
    names = {w[0] for w in work}
    assert names & set(COMPILED), "workload must include compiled kernels"
    outs = {}
    for policy in POLICY_NAMES:
        srv = rt.RuntimeServer(n_sm=2, policy=policy)
        tickets = {}
        for i, (name, mod, n, code, (grid, bd), g0) in enumerate(work):
            t = srv.submit(code, grid, bd, g0.copy(),
                           client=f"t{i % 3}")
            tickets[t] = (name, mod, n, g0)
        results, _ = srv.drain()
        for t, (name, mod, n, g0) in tickets.items():
            np.testing.assert_array_equal(
                results[t].gmem[mod.out_slice(n)], mod.oracle(g0, n))
        outs[policy] = {i: results[t].gmem
                        for i, t in enumerate(sorted(tickets))}
    base = outs["monolithic"]
    for policy in POLICY_NAMES[1:]:
        for i in base:
            np.testing.assert_array_equal(outs[policy][i], base[i])


def test_compiled_kernel_footprint_diversity_in_drain():
    """A mixed drain of the three compiled kernels occupies at least
    three distinct gmem buckets (the heterogeneity the cost model and
    BalancedDrain exist to chew on)."""
    srv = rt.RuntimeServer(n_sm=2, policy="bucket")
    for i, (name, n) in enumerate(
            (("histogram", 64), ("scan", 128), ("spmv", 64))):
        mod, code, g0, _ = _seq(name, n, gseed=i)
        srv.submit(code, *mod.launch(n), g0.copy(), client=f"c{i}")
    _, stats = srv.drain()
    assert len(stats.by_bucket) >= 3, sorted(stats.by_bucket)


def test_compiled_kernels_feed_cost_model():
    """Completed drains of a compiled kernel tighten the registry's
    duration prediction from the program-length seed to observed
    cycles."""
    srv = rt.RuntimeServer(n_sm=1, policy="balanced")
    mod, code, g0, _ = _seq("histogram", 64)
    m = srv.registry.load(code, "histogram")
    before = srv.registry.cost_model.estimate(m)
    assert not before.observed
    srv.submit(m, *mod.launch(64), g0.copy())
    srv.drain()
    after = srv.registry.cost_model.estimate(m)
    assert after.observed and after.samples >= 1
    assert after.cycles_per_block != before.cycles_per_block
