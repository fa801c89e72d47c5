"""y = A x for an n x n CSR matrix with 1% nonzeros, one thread per row.

SHOC's ``Spmv`` (CSR-scalar kernel): 128 threads per block, the row
loop of thread ``r`` runs from ``row_ptr[r]`` to ``row_ptr[r + 1]``.
``ceil(n * n / 100)`` positions are drawn uniformly without
replacement.  SHOC seeds its pattern generator with a constant
(8675309), so every run of a size multiplies the same pattern; so does
this one, with the values and x, in [-100, 100), drawn from the run's
generator.  int32 with wraparound.

Memory (words): ``[vals (nnz) | cols (nnz) | row_ptr (n + 1) | x (n) |
y (n)]``.
"""
import functools

import numpy as np

PATTERN_SEED = 8675309


def build(n):
    from repro.compiler.kernels import spmv_csr
    return spmv_csr.build(n)


def launch(n):
    from repro.compiler.kernels import spmv_csr
    return spmv_csr.launch(n)


def nnz(n):
    return (n * n + 99) // 100


def _at(n):
    """Word offsets of row_ptr, x and y."""
    rp = 2 * nnz(n)
    return rp, rp + n + 1, rp + 2 * n + 1


@functools.lru_cache(maxsize=None)
def pattern(n):
    """``(cols, row_ptr)`` of the size's one pattern."""
    rng = np.random.default_rng(np.random.SeedSequence([PATTERN_SEED, n]))
    k, total = nnz(n), n * n
    # the distinct values of i.i.d. draws are a uniform subset of their
    # size; dropping a uniform few leaves a uniform subset of size k
    m = k + 2 * (k * k // total) + 64
    while True:
        d = np.sort(rng.integers(0, total, m, dtype=np.int64))
        u = d[np.concatenate(([True], d[1:] != d[:-1]))]
        if u.size >= k:
            break
        m *= 2
    keep = np.ones(u.size, bool)
    keep[rng.choice(u.size, u.size - k, replace=False)] = False
    pos = u[keep]
    return ((pos % n).astype(np.int32),
            np.searchsorted(pos, np.arange(n + 1) * n).astype(np.int32))


def make_gmem(rng, n):
    k = nnz(n)
    rp, x_at, y_at = _at(n)
    cols, row_ptr = pattern(n)
    g = np.zeros(y_at + n, np.int32)
    g[:k] = rng.integers(-100, 100, k, dtype=np.int32)
    g[k:rp] = cols
    g[rp:x_at] = row_ptr
    g[x_at:y_at] = rng.integers(-100, 100, n, dtype=np.int32)
    return g


def out_slice(n):
    y_at = _at(n)[2]
    return slice(y_at, y_at + n)


def oracle(g0, n):
    k = nnz(n)
    rp, x_at, y_at = _at(n)
    row_ptr = g0[rp:x_at].astype(np.int64)
    prod = g0[:k].astype(np.int64) * g0[x_at:y_at].astype(np.int64)[g0[k:rp]]
    y = np.zeros(n, np.int64)
    np.add.at(y, np.repeat(np.arange(n), np.diff(row_ptr)), prod)
    return (((y + 2**31) % 2**32) - 2**31).astype(np.int32)
