"""Bitonic sort of n values in shared memory, one block of n threads."""
import numpy as np


def build(n):
    from repro.core.programs import bitonic
    return bitonic.build(n)


def launch(n):
    return (1, 1), (n, 1)


def make_gmem(rng, n):
    g = np.zeros(2 * n, np.int32)
    g[:n] = rng.integers(-10000, 10000, n, dtype=np.int32)
    return g


def out_slice(n):
    return slice(n, 2 * n)


def oracle(g0, n):
    return np.sort(g0[:n]).astype(np.int32)
