"""The CSR SpMV cell's plain references, and its fault: a row loop that
ends one nonzero early must read ``correct: false``."""
import json

import jax
import numpy as np

from bench.tests.conftest import REPO, TINY_MACHINE, add_cell, run_cell


def test_oracle_agrees_with_the_program_and_the_interpreter():
    """At n = 256 the benchmark's oracle, the program's oracle and the
    numpy interpreter running the served binary give one answer, on the
    benchmark's memory and on the program's own draw."""
    from bench import simt_ref
    from bench.kernels import spmv_csr as ref
    from repro.compiler.kernels import spmv_csr as prog
    n = 256
    for seed in (0, 2**31 + 5):
        for g0 in (ref.make_gmem(np.random.default_rng(seed), n),
                   prog.make_gmem(np.random.default_rng(seed), n)):
            want = ref.oracle(g0, n)
            np.testing.assert_array_equal(prog.oracle(g0, n), want)
            got = simt_ref.run_launch(ref.build(n), *ref.launch(n), g0)
            np.testing.assert_array_equal(got["gmem"][ref.out_slice(n)],
                                          want)
            rows = np.diff(g0[2 * ref.nnz(n):2 * ref.nnz(n) + n + 1])
            assert rows.sum() == ref.nnz(n) and rows.max() > rows.min()


def test_one_pattern_a_size_values_from_the_seed():
    """As SHOC's constant-seeded generator: every seed multiplies the
    same pattern, with other values and x."""
    from bench.kernels import spmv_csr as ref
    n = 1024
    a, b = (ref.make_gmem(np.random.default_rng(s), n) for s in (1, 2))
    rp = 2 * ref.nnz(n)
    np.testing.assert_array_equal(a[ref.nnz(n):rp + n + 1],
                                  b[ref.nnz(n):rp + n + 1])
    assert (a[:ref.nnz(n)] != b[:ref.nnz(n)]).any()
    np.testing.assert_array_equal(
        a, ref.make_gmem(np.random.default_rng(1), n))


def _tiny_csr(root):
    config = json.loads(
        (REPO / "bench" / "configs" / "shoc-spmv-csr-2sm.json").read_text())
    config.update(name="tiny-csr", machine=TINY_MACHINE,
                  sizes={"spmv_csr": [128, 256]})
    return add_cell(root, config, "batch")


def test_tiny_cell_is_correct_and_reads_lane_use(tiny_root):
    cell = _tiny_csr(tiny_root)
    rc, line, err = run_cell(tiny_root, cell, trace=1)
    assert rc == 0 and line["correct"], err
    got = line["metrics"]
    assert 0 < got["simt_lane_use"]["value"] < 100
    assert 0 < got["smstep_lane_use"]["value"] <= 100


def test_loop_one_nonzero_short_is_not_correct(tiny_root, monkeypatch):
    """The served binary's row loop stops before the row's last
    nonzero.  The interpreter runs the same binary, so the oracle
    words catch it."""
    from repro.compiler.kernels import spmv_csr as prog

    def short(k, bd, cols_at, rp_at, x_at, y_at):
        r = k.blockIdx.x * bd + k.threadIdx.x
        acc = k.var(0)
        with k.for_(k.gmem[rp_at + r], k.gmem[rp_at + 1 + r] - 1) as j:
            acc.set(acc + k.gmem[j] * k.gmem[x_at + k.gmem[cols_at + j]])
        k.gmem[y_at + r] = acc
    cell = _tiny_csr(tiny_root)
    jax.clear_caches()
    monkeypatch.setattr(prog, "kernel", short)
    rc, line, err = run_cell(tiny_root, cell)
    assert rc == 0 and line is not None, err
    assert not line["correct"]
    assert line["checks"]["oracle_words_wrong"]["value"] > 0
