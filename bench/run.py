#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``<config>.<traffic>`` in ``BENCHMARK.json``) is read from
files: the configuration, the traffic mix, the weights and one reader
per metric.  The run builds its inputs from ``--seed``, builds the
served system and warms every compiled shape its traffic reaches
(set-up), measures for ``--seconds``, then checks every result against
plain references (``bench/check.py``).  With ``--trace 1`` the program's
tracer is on, a slice of the window runs under the JAX profiler, and
the per-layer metrics are reported instead of the end-to-end ones.

The last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown``
(traced runs) and, last, ``checks``: every number compared with its
limit.  The same numbers end standard error.  The run refuses (exit 3,
no result) unless JAX sees a TPU and as many chips as the cell asks.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root=ROOT, require_tpu=True, compile_cache=True,
         out=sys.stdout, err=sys.stderr) -> int:
    args = _args(argv)
    sys.path[:0] = [str(root), str(root / "src")]
    from bench import spec
    cell = spec.Cell(root, args.workload)

    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); "
              f"JAX sees {len(devices)} {devices[0].platform} device(s)",
              file=err)
        return 3
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **_: compiles.append((time.perf_counter(), d))
        if ev == _COMPILE_EVENT else None)
    if compile_cache:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()

    from repro import obs
    from bench import check, drive, trace_reduce
    from bench import workload as wl

    dev = devices[0]
    peaks = None
    if args.trace:
        table = spec.load_json(root / "bench" / "peaks.json")
        if dev.device_kind not in table:
            print(f"bench: no peaks for device kind {dev.device_kind!r} "
                  "in bench/peaks.json", file=err)
            return 4
        peaks = table[dev.device_kind]

    cfg, traffic = cell.config, cell.traffic
    items = wl.load_items(cfg, cell.weights)
    tracer = obs.Tracer()          # started with the window when traced
    server = drive.make_server(cfg, tracer)
    run = drive.Run(mode=traffic["mode"], seconds=args.seconds, items=items,
                    machine=cfg["machine"], tracer=tracer,
                    compiles=compiles, peaks=peaks)
    profiler = None
    if args.trace:
        log_dir = Path(root) / ".bench_out" / f"trace-{cell.name}-{args.seed}"
        shutil.rmtree(log_dir, ignore_errors=True)
        profiler = drive.Profiler(log_dir, traffic.get("trace_slice_s", 1.0))

    if run.mode == "batch":
        drive.warm(server, items, [1] * len(items))
        drive.run_batch(server, items, args.seed, args.seconds, run,
                        profiler)
    else:
        share = wl.popularity(cfg, items)
        drive.warm(server, items, wl.warm_batches(share, server.max_batch))
        schedule = drive.open_launches(items, share, wl.rate(cfg, traffic),
                                       args.seconds, args.seed)
        drive.run_open(server, items, schedule, args.seconds, run,
                       profiler)
    run.setup_s = run.t0 - T_START

    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    breakdown = None
    if profiler is not None and profiler.state == "done":
        ex = trace_reduce.extract(profiler.log_dir,
                                  host_ops=not require_tpu)
        to_ns = trace_reduce.clock(ex, profiler.mark_t)
        if to_ns is not None:
            run.to_ns = to_ns
            run.slice = (profiler.slice_t, profiler.stop_t)
            run.trace = trace_reduce.reduce(
                ex, (to_ns(profiler.slice_t), to_ns(profiler.stop_t)),
                trace_reduce.span_intervals(tracer, run.span_base, to_ns))
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
            breakdown = {"device_ops": run.trace["device_ops"],
                         "idle_gaps": run.trace["idle_gaps"]}
        size = sum(p.stat().st_size for p in Path(profiler.log_dir)
                   .rglob("*") if p.is_file())
        print(f"bench: trace of {run.trace['window_s'] if run.trace else 0:.3f}"
              f" s, {len(ex['ops'])} device ops, {size} bytes on disk",
              file=err)
        shutil.rmtree(profiler.log_dir, ignore_errors=True)

    # everything the window produced is read back only now
    del server
    numbers = check.compare(run, args.seed, traffic.get("ref_sample")
                            or len(run.launches))
    correct = check.passed(numbers)

    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        v = cell.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    _report(run, err)
    counts = {n: v for n, v, _, _ in numbers}
    failed = counts["launches_failed"] + counts["launches_unresolved"]
    line = {"correct": bool(correct), "attempted": len(run.launches),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {n: {"value": v, "limit": lim, "bound": kind}
                      for n, v, kind, lim in numbers}
    for n, v, kind, lim in numbers:
        print(f"check {n} = {v} ({kind} {lim})", file=err)
    err.flush()
    print(json.dumps(line), file=out, flush=True)
    return 0


def _report(run, err) -> None:
    """Context for the reader: lateness, compiles, dispatch groups and
    the length of each batch pass."""
    import numpy as np
    late = [(r.t_sub0 - r.due) * 1e3 for r in run.launches]
    in_window = sum(run.t0 <= t <= run.t1 for t, _ in run.compiles)
    groups = 0
    if run.tracer is not None and run.tracer.roots:
        groups = len(run.tracer.find("dispatch"))
    print(f"bench: mode={run.mode} launches={len(run.launches)} "
          f"passes={len(run.passes)} window_s={run.t1 - run.t0:.3f} "
          f"setup_s={run.setup_s:.3f} compiles_in_window={in_window} "
          f"dispatch_groups={groups} "
          f"lateness_ms_p50={np.percentile(late, 50):.3f} "
          f"p95={np.percentile(late, 95):.3f} max={max(late):.3f} "
          f"pass_s={','.join(f'{b - a:.3f}' for a, b in run.passes)}",
          file=err)


if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.exit(main())
