"""CSR sparse matrix-vector product, one thread per row, DSL-compiled.

The scalar CSR kernel of Bell & Garland ("Efficient Sparse Matrix-Vector
Multiplication on CUDA", NVIDIA NVR-2008-004), which the SHOC suite's
``Spmv`` benchmark runs: thread ``r`` walks its own row from
``row_ptr[r]`` to ``row_ptr[r + 1]``, so every lane has its own trip
count and the row loop is a *divergent loop* (one SSY before it, one
``.S`` exit after its test; see :class:`repro.compiler.dsl._For`).  The
``x[col[j]]`` loads gather at data-dependent addresses.  Values are
int32 with wraparound: the ISA is integer-only.

The matrix is SHOC's random one: 1% of the ``n * n`` positions, rounded
up, drawn uniformly without replacement, so row lengths are close to
Binomial(n, 0.01).  Values and ``x`` lie in [-100, 100).

Global memory layout (words), ``nnz = nnz_of(n)``::

    [0, nnz)                      values, row by row
    [nnz, 2 nnz)                  column indices
    [2 nnz, 2 nnz + n + 1)        row_ptr
    [.. , .. + n)                 x
    [.. , .. + n)                 y (output)
"""
import numpy as np

from ... import compiler

BD = 128          # threads (rows) per block; a smaller matrix, one block


def nnz_of(n: int) -> int:
    """Nonzeros of the n x n matrix: 1% of its positions, rounded up."""
    return (n * n + 99) // 100


def kernel(k, bd, cols_at, rp_at, x_at, y_at):
    r = k.blockIdx.x * bd + k.threadIdx.x
    acc = k.var(0)
    with k.for_(k.gmem[rp_at + r], k.gmem[rp_at + 1 + r]) as j:
        acc.set(acc + k.gmem[j] * k.gmem[x_at + k.gmem[cols_at + j]])
    k.gmem[y_at + r] = acc


def _layout(n: int) -> dict:
    nnz = nnz_of(n)
    return {"cols_at": nnz, "rp_at": 2 * nnz, "x_at": 2 * nnz + n + 1,
            "y_at": 2 * nnz + 2 * n + 1}


def _bd(n: int) -> int:
    bd = min(n, BD)
    assert n % bd == 0 and bd % 32 == 0, \
        f"spmv_csr n={n} must be a multiple of 32 up to {BD}, of {BD} above"
    return bd


def _params(n: int) -> dict:
    return {"bd": _bd(n), **_layout(n)}


def build(n: int, optimize: bool = True) -> np.ndarray:
    return compiler.compile_kernel(kernel, _params(n), name="spmv_csr",
                                   optimize=optimize).code


def report(n: int = 128) -> compiler.CompileReport:
    return compiler.compile_report(kernel, _params(n), name="spmv_csr")


def launch(n: int):
    return (n // _bd(n), 1), (_bd(n), 1)


def n_threads(n: int) -> int:
    return n


def positions(rng: np.random.Generator, n: int) -> np.ndarray:
    """``nnz_of(n)`` distinct positions ``row * n + col``, ascending,
    uniform over the subsets of that size.  The distinct values of
    i.i.d. draws are a uniform subset of their size; a uniform few of
    them are dropped to leave exactly ``nnz``.  Sorting is the only
    O(nnz log nnz) step."""
    total, nnz = n * n, nnz_of(n)
    m = nnz + 2 * (nnz * nnz // total) + 64
    dtype = np.int32 if total < 2**31 else np.int64
    while True:
        d = np.sort(rng.integers(0, total, m, dtype=dtype))
        u = d[np.concatenate(([True], d[1:] != d[:-1]))]
        if u.size >= nnz:
            break
        m *= 2
    keep = np.ones(u.size, bool)
    keep[rng.choice(u.size, u.size - nnz, replace=False)] = False
    return u[keep]


def pack(n: int, row_ptr, cols, vals, x) -> np.ndarray:
    """The launch's global memory from a CSR matrix and ``x``."""
    lay = _layout(n)
    g = np.zeros(lay["y_at"] + n, np.int32)
    nnz = nnz_of(n)
    assert len(vals) == len(cols) == nnz == row_ptr[-1], \
        f"spmv_csr n={n} holds exactly {nnz} nonzeros"
    g[:nnz] = vals
    g[nnz:2 * nnz] = cols
    g[lay["rp_at"]:lay["x_at"]] = row_ptr
    g[lay["x_at"]:lay["y_at"]] = x
    return g


def make_gmem(rng: np.random.Generator, n: int) -> np.ndarray:
    p = positions(rng, n)
    row_ptr = np.searchsorted(p, np.arange(n + 1) * n)
    nnz = nnz_of(n)
    return pack(n, row_ptr, p % n,
                rng.integers(-100, 100, nnz, dtype=np.int32),
                rng.integers(-100, 100, n, dtype=np.int32))


def out_slice(n: int) -> slice:
    y_at = _layout(n)["y_at"]
    return slice(y_at, y_at + n)


def oracle(gmem0: np.ndarray, n: int) -> np.ndarray:
    lay, nnz = _layout(n), nnz_of(n)
    vals = gmem0[:nnz].astype(np.int64)
    cols = gmem0[nnz:2 * nnz]
    row_ptr = gmem0[lay["rp_at"]:lay["x_at"]].astype(np.int64)
    x = gmem0[lay["x_at"]:lay["y_at"]].astype(np.int64)
    rows = np.repeat(np.arange(n), np.diff(row_ptr))
    y = np.zeros(n, np.int64)
    np.add.at(y, rows, vals * x[cols])
    return (((y + 2**31) % 2**32) - 2**31).astype(np.int32)
