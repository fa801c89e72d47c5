"""out = in.T for an n x n int32 matrix, 16 x 16 thread blocks."""
import numpy as np

TILE = 16


def build(n):
    from repro.core.programs import transpose
    return transpose.build(n)


def launch(n):
    return (n // TILE, n // TILE), (TILE, TILE)


def make_gmem(rng, n):
    g = np.zeros(2 * n * n, np.int32)
    g[:n * n] = rng.integers(-1000, 1000, n * n, dtype=np.int32)
    return g


def out_slice(n):
    return slice(n * n, 2 * n * n)


def oracle(g0, n):
    return g0[:n * n].reshape(n, n).T.ravel().astype(np.int32)
