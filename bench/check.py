"""Decides ``correct``: the served results against plain references.

Two references, neither importing the program:

* each kernel's numpy oracle (``bench.kernels.<kernel>.oracle``) gives
  the words a launch must write; every completed launch is compared;
* the numpy interpreter (``bench.simt_ref``) gives a launch's whole
  final memory and its counters (per-opcode issues and lanes, cycles
  per block, stack pushes and pops, stack high-water mark, overflow);
  it runs over a sample of the completed launches drawn from the seed,
  one of every item served and the rest at random.

Each number compared has the limit 0: the machine is integer and the
runtime promises bit-exact memory and counters.
"""
from __future__ import annotations

import numpy as np

from bench import simt_ref

COUNTERS = ("op_issues", "op_lanes", "cycles_per_block", "stack_ops",
            "max_sp", "overflow")


def sample(run, seed: int, size: int) -> list:
    """Completed launches the interpreter checks: per item one drawn
    from the seed, then more at random up to ``size`` in all."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1 << 21]))
    done = [r for r in run.launches if r.result is not None]
    order = rng.permutation(len(done))
    first = {}
    for j in order:
        first.setdefault(done[j].item, j)
    rest = [j for j in order if j not in set(first.values())]
    chosen = list(first.values()) + rest[:max(0, size - len(first))]
    return [done[j] for j in sorted(chosen)]


def reference(item, gmem0, machine: dict, bits: int = 32) -> dict:
    return simt_ref.run_launch(item.code, item.grid, item.block_dim, gmem0,
                               bits=bits, **machine)


def compare(run, seed: int, size: int) -> list:
    """``[(name, value, "max" or "min", limit)]``: every number the
    check compares, with the bound it must keep."""
    oracle_words = 0
    done = [r for r in run.launches if r.result is not None]
    for r in done:
        item = run.items[r.item]
        got = np.asarray(r.result.gmem)[item.kernel.out_slice(item.n)]
        want = item.kernel.oracle(r.gmem0, item.n)
        oracle_words += int(np.count_nonzero(got != want)) + \
            abs(got.size - want.size)
    ref_words = counter_fields = 0
    for r in sample(run, seed, size):
        item = run.items[r.item]
        ref = reference(item, r.gmem0, run.machine)
        got = np.asarray(r.result.gmem)
        ref_words += int(np.count_nonzero(got != ref["gmem"])) \
            if got.shape == ref["gmem"].shape else ref["gmem"].size
        for f in COUNTERS:
            a = np.asarray(getattr(r.result, f), np.int64)
            b = np.asarray(ref[f], np.int64)
            counter_fields += int(a.shape != b.shape or
                                  not np.array_equal(a, b))
    failed = sum(r.error is not None for r in run.launches)
    unresolved = sum(r.t_done is None for r in run.launches)
    return [("oracle_words_wrong", oracle_words, "max", 0),
            ("ref_words_wrong", ref_words, "max", 0),
            ("counter_fields_wrong", counter_fields, "max", 0),
            ("launches_failed", failed, "max", 0),
            ("launches_unresolved", unresolved, "max", 0),
            ("launches_completed", len(done), "min", 1)]


def passed(numbers: list) -> bool:
    return all(v <= lim if kind == "max" else v >= lim
               for _, v, kind, lim in numbers)
