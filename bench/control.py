#!/usr/bin/env python3
"""The check's control: the plain reference, one precision down, put
in the program's place.  It must come out not correct.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds S]

The configuration states 32-bit integer registers; the control runs
every launch a cell's window would serve through the numpy interpreter
with 16-bit registers (``bench.simt_ref``, ``bits=16``) and hands those
results to the same comparison a run makes (``bench.check``).  A batch
cell serves ``--passes`` passes; an open-loop cell its whole schedule
for ``--seconds``.  No device is used.  One JSON line per seed.
"""
import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]


def control_run(cell, seed: int, seconds: float, passes: int, bits: int):
    from bench import check, drive
    from bench import workload as wl
    cfg, traffic = cell.config, cell.traffic
    items = wl.load_items(cfg, cell.weights)
    run = drive.Run(mode=traffic["mode"], seconds=seconds, items=items,
                    machine=cfg["machine"])
    if run.mode == "batch":
        launches = [drive.Launch(i, k * len(items) + i,
                                 it.inputs(seed, k * len(items) + i))
                    for k in range(passes) for i, it in enumerate(items)]
    else:
        launches = drive.open_launches(items, wl.popularity(cfg, items),
                                       wl.rate(cfg, traffic), seconds, seed)
    for r in launches:
        out = check.reference(items[r.item], r.gmem0, run.machine, bits=bits)
        r.result = SimpleNamespace(**out)
        r.t_done = r.due
    run.launches = launches
    run.t1 = max(r.due for r in launches)
    return check.compare(run, seed, traffic.get("ref_sample")
                         or len(launches))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--bits", type=int, default=16)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import check, spec
    cell = spec.Cell(ROOT, args.workload)
    seconds = args.seconds or cell.run_seconds
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers = control_run(cell, seed, seconds, args.passes, args.bits)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "bits": args.bits,
                          "correct": check.passed(numbers),
                          "numbers": {n: v for n, v, _, _ in numbers}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
