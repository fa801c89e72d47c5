#!/usr/bin/env python3
"""Smoke run of the served path on the TPU, through its normal entry points.

    python3 chip_smoke.py               # phases (a) and (b), on one chip
    python3 chip_smoke.py --four-chips  # phase (c) alone, on four chips

(a) The paper suite at the paper's size: autocorr, bitonic, matmul,
    reduction and transpose at n = 256 (matmul is a 16x16 grid), plus the
    DSL-compiled histogram, scan and spmv at their largest serving sizes.
    Several tenants submit them to ``RuntimeServer(n_sm=2,
    resident_gmem=True)``, which drains them through submit -> policy ->
    executor.  Every ticket's memory must match the kernel's numpy oracle
    bit for bit, every future must resolve, and the executed per-SM cycles
    must equal the analytical ``GridResult`` replay.
(b) A few seconds of always-on serving: a ``ServingLoop`` driven by the
    seeded open-loop load generator over the mixed serving workload, with
    no window error, no unresolved future and no mismatch against the
    sequential reference (itself checked against the numpy oracles).
(c) The work of (a) drained over four chips with ``shard_sm=True`` and on
    one with ``shard_sm=False``: the sharded drain must really place the
    SM axis on four devices and match the unsharded one bit for bit.

It prints one line per phase (wall time, compile time, set-up time,
launches, bit-exactness) and then, as its last line, one JSON object
naming the device.  It refuses to run unless JAX's first device is a TPU,
and any failed check exits non-zero.  Everything runs in this one
process; data comes from a fixed seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro import runtime as rt  # noqa: E402
from repro.core.programs import ALL  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.gpgpu_serve import (SIZES, build_tenants,  # noqa: E402
                                      build_workload, loadgen_pool,
                                      workload_kernels)

PAPER_N = 256          # the paper's largest input size (Figs. 4-5)
SEED = 0
TENANTS = 4
SERVE_N_SM = 2
FOUR_CHIP_N_SM = 4
LOADGEN_LAUNCHES = 16  # work items in the serving pool
LOADGEN_RATE_HZ = 8.0
LOADGEN_DURATION_S = 3.0

#: JAX's timer around each XLA compile; a persistent-cache hit is timed
#: under it too, so a warm cache shows as a smaller ``compile_s``
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_s = 0.0


def _on_duration(event: str, duration: float, **_) -> None:
    global _compile_s
    if event == _COMPILE_EVENT:
        _compile_s += duration


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_seconds() -> float:
    """Seconds spent in XLA compiles (or cache fetches) so far."""
    return _compile_s


def paper_work(n: int = PAPER_N, seed: int = SEED):
    """The paper's five kernels at ``n`` and the DSL kernels at their
    largest serving size, as ``build_workload`` tuples."""
    pool = workload_kernels()
    work = []
    for i, name in enumerate(sorted(pool)):
        mod = pool[name]
        size = n if name in ALL else max(SIZES[name])
        work.append((name, mod, size, mod.build(size), mod.launch(size),
                     mod.make_gmem(np.random.default_rng(seed + i), size)))
    return work


def drain_suite(work, n_sm: int, shard_sm: bool = False):
    """Submit ``work`` from ``TENANTS`` tenants and drain it once.

    Returns ``(server, stats, results)`` with one result per item, in
    order; every future must have resolved without error."""
    srv = rt.RuntimeServer(n_sm=n_sm, policy="bucket", resident_gmem=True,
                           shard_sm=shard_sm,
                           metrics=obs.MetricsRegistry())
    futs = [srv.submit_future(code, grid, bd, g0,
                              client=f"tenant{i % TENANTS}")
            for i, (_, _, _, code, (grid, bd), g0) in enumerate(work)]
    results, stats = srv.drain()
    for fut in futs:
        if not fut.done():
            raise AssertionError(f"ticket {fut.ticket} unresolved")
        fut.result()                 # raises the launch's own error
    return srv, stats, [results[f.ticket] for f in futs]


def check_oracles(work, results) -> None:
    """Every launch's output words equal its kernel's numpy oracle."""
    for (name, mod, n, _, _, g0), res in zip(work, results):
        got = np.asarray(res.gmem)[mod.out_slice(n)]
        if not np.array_equal(got, mod.oracle(g0, n)):
            raise AssertionError(f"{name} n={n}: gmem differs from its "
                                 "numpy oracle")


def check_replay(stats, results, n_sm: int) -> None:
    """Executed per-SM cycles equal the analytical round-robin replay.

    The bucket policy gives each distinct binary its own dispatch group,
    so the drain's counters are the sum of the per-launch replays."""
    if stats.n_sub_batches != len(results):
        raise AssertionError(f"{stats.n_sub_batches} dispatch groups for "
                             f"{len(results)} distinct binaries")
    want = sum(r.per_sm_cycles(n_sm) for r in results)
    if not np.array_equal(stats.per_sm_cycles, want):
        raise AssertionError(f"executed per-SM cycles "
                             f"{stats.per_sm_cycles.tolist()} != "
                             f"analytical {want.tolist()}")


def _phase_line(phase: str, t0: float, c0: float, setup_s: float,
                **fields) -> dict:
    row = {"phase": phase, "wall_s": time.perf_counter() - t0,
           "compile_s": compile_seconds() - c0, "setup_s": setup_s,
           **fields}
    print(" ".join(f"{k}={v}" for k, v in row.items()), flush=True)
    return row


def phase_paper_suite(n: int = PAPER_N, n_sm: int = SERVE_N_SM) -> dict:
    """(a): the paper suite through one multi-tenant drain."""
    t0, c0 = time.perf_counter(), compile_seconds()
    work = paper_work(n)
    setup_s = time.perf_counter() - t0
    _, stats, results = drain_suite(work, n_sm)
    check_oracles(work, results)
    check_replay(stats, results, n_sm)
    return _phase_line("a_paper_suite", t0, c0, setup_s,
                       n=n, launches=stats.n_launches,
                       blocks=stats.n_blocks,
                       per_sm_cycles=stats.per_sm_cycles.tolist(),
                       bit_exact=True)


def phase_serving(duration_s: float = LOADGEN_DURATION_S,
                  rate_hz: float = LOADGEN_RATE_HZ,
                  n_sm: int = SERVE_N_SM) -> dict:
    """(b): open-loop load against an always-on ``ServingLoop``."""
    t0, c0 = time.perf_counter(), compile_seconds()
    work = build_workload(LOADGEN_LAUNCHES, SEED)
    pool = loadgen_pool(work)        # expected gmem: sequential run_grid
    for (name, mod, n, _, _, g0), item in zip(work, pool):
        if not np.array_equal(item.expected_gmem[mod.out_slice(n)],
                              mod.oracle(g0, n)):
            raise AssertionError(f"{name} n={n}: sequential reference "
                                 "differs from the numpy oracle")
    setup_s = time.perf_counter() - t0
    srv = rt.RuntimeServer(n_sm=n_sm, resident_gmem=True,
                           metrics=obs.MetricsRegistry())
    arrivals = rt.build_arrivals(build_tenants(TENANTS, rate_hz),
                                 duration_s, len(pool), seed=SEED)
    with rt.ServingLoop(srv) as loop:
        report = rt.run_open_loop(loop, pool, arrivals)
    bad = {k: getattr(report, k) for k in
           ("loop_window_errors", "unresolved", "mismatched", "failed",
            "rejected", "shed")}
    if any(bad.values()) or report.completed != report.submitted \
            or report.completed == 0:
        raise AssertionError(f"serving run not clean: {bad}, "
                             f"{report.completed}/{report.submitted} "
                             "completed")
    return _phase_line("b_serving", t0, c0, setup_s,
                       launches=report.completed,
                       window_errors=report.loop_window_errors,
                       unresolved=report.unresolved,
                       mismatched=report.mismatched, bit_exact=True)


def phase_four_chips(n: int = PAPER_N,
                     n_sm: int = FOUR_CHIP_N_SM) -> dict:
    """(c): the paper suite sharded over four devices against one."""
    t0, c0 = time.perf_counter(), compile_seconds()
    work = paper_work(n)
    setup_s = time.perf_counter() - t0
    srv, sharded, res_s = drain_suite(work, n_sm, shard_sm=True)
    if srv.n_devices != 4 or sharded.n_devices != 4:
        raise AssertionError(f"shard_sm placed the SM axis on "
                             f"{sharded.n_devices} devices, not 4")
    _, single, res_u = drain_suite(work, n_sm, shard_sm=False)
    check_oracles(work, res_s)
    for (name, *_), a, b in zip(work, res_s, res_u):
        if not np.array_equal(np.asarray(a.gmem), np.asarray(b.gmem)):
            raise AssertionError(f"{name}: sharded gmem differs")
    if not np.array_equal(sharded.per_sm_cycles, single.per_sm_cycles):
        raise AssertionError("sharded per-SM cycles differ")
    return _phase_line("c_four_chips", t0, c0, setup_s, n=n,
                       launches=sharded.n_launches,
                       n_devices=sharded.n_devices,
                       per_sm_cycles=sharded.per_sm_cycles.tolist(),
                       bit_exact=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only phase (c), the four-chip sharded "
                         "drain against the unsharded one")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: the first JAX device is {dev.platform!r}, "
              "not a TPU; refusing to run", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.four_chips:
        if len(jax.devices()) < 4:
            print(f"chip_smoke: --four-chips needs 4 devices, found "
                  f"{len(jax.devices())}", file=sys.stderr)
            return 2
        count = phase_four_chips()["n_devices"]
    else:
        phase_paper_suite()
        phase_serving()
        count = 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
