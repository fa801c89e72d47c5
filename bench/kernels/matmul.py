"""C = A @ B on n x n int32 matrices, 16 x 16 shared-memory tiles."""
import numpy as np

TILE = 16


def build(n):
    from repro.core.programs import matmul
    return matmul.build(n)


def launch(n):
    return (n // TILE, n // TILE), (TILE, TILE)


def make_gmem(rng, n):
    g = np.zeros(3 * n * n, np.int32)
    g[:2 * n * n] = rng.integers(-64, 64, 2 * n * n, dtype=np.int32)
    return g


def out_slice(n):
    return slice(2 * n * n, 3 * n * n)


def oracle(g0, n):
    a = g0[:n * n].reshape(n, n).astype(np.int64)
    b = g0[n * n:2 * n * n].reshape(n, n).astype(np.int64)
    return ((((a @ b) + 2**31) % 2**32) - 2**31).astype(np.int32).ravel()
