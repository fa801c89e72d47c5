"""Autocorrelation r[k] = sum_i x[i] x[i+k], one thread per lag."""
import numpy as np

BD = 64


def build(n):
    from repro.core.programs import autocorr
    return autocorr.build(n)


def launch(n):
    return (max(1, -(-n // BD)), 1), (min(BD, n), 1)


def make_gmem(rng, n):
    g = np.zeros(2 * n, np.int32)
    g[:n] = rng.integers(-100, 100, n, dtype=np.int32)
    return g


def out_slice(n):
    return slice(n, 2 * n)


def oracle(g0, n):
    x = g0[:n].astype(np.int64)
    r = np.array([np.sum(x[:n - k] * x[k:]) for k in range(n)])
    return (((r + 2**31) % 2**32) - 2**31).astype(np.int32)
