#!/usr/bin/env python3
"""Writes ``bench/weights/<config>.json``: per item, the reference
warp-instructions of one launch (the sum of its per-opcode issues),
from the plain interpreter on the seed-0 input.

    python3 bench/make_weights.py <config-file> [--out PATH]

A weight is fixed when its cell is defined and never changes after the
cell is accepted: it is the unit of work ``winstr_per_s`` counts, so a
program change that shortens a binary shows as launches finishing
sooner, not as less work.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def weights(config: dict, machine: dict = None) -> dict:
    from bench import simt_ref
    from bench import workload as wl
    out = {}
    for it in wl.load_items(config, None):
        ref = simt_ref.run_launch(it.code, it.grid, it.block_dim,
                                  it.inputs(0, 0),
                                  **(machine or config["machine"]))
        out[it.key] = int(ref["op_issues"].sum())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    path = Path(args.config)
    with open(path) as f:
        config = json.load(f)
    w = weights(config)
    out = Path(args.out) if args.out else \
        ROOT / "bench" / "weights" / path.name
    with open(out, "w") as f:
        json.dump(w, f, indent=1, sort_keys=True)
        f.write("\n")
    print(json.dumps(w))
    return 0


if __name__ == "__main__":
    sys.exit(main())
