"""Sum of n values: a shared-memory tree, one pass (n <= 256)."""
import numpy as np

BD = 128
IN_AT = 16     # inputs follow a 16-word parameter block; gmem[0] = n


def build(n):
    from repro.core.programs import reduction
    return reduction.build(n)


def launch(n):
    return (max(1, -(-n // (2 * BD))), 1), (min(BD, max(32, n // 2)), 1)


def make_gmem(rng, n):
    blocks = launch(n)[0][0]
    g = np.zeros(IN_AT + n + blocks, np.int32)
    g[0] = n
    g[IN_AT:IN_AT + n] = rng.integers(-1000, 1000, n, dtype=np.int32)
    return g


def out_slice(n):
    return slice(IN_AT + n, IN_AT + n + 1)


def oracle(g0, n):
    return np.array([g0[IN_AT:IN_AT + n].astype(np.int64).sum()], np.int32)
