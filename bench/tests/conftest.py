"""CPU rehearsal of the benchmark: tiny cells in a copy of the tree.

Run with ``JAX_PLATFORMS=cpu python -m pytest bench/tests``.  These
tests are not part of the repository's tier-1 suite.
"""
import io
import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

TINY_MACHINE = {"n_sp": 8, "n_regs": 16, "warp_stack_depth": 32,
                "enable_mul": True, "num_read_operands": 3,
                "smem_words": 4096, "mem_latency_global": 8,
                "mem_latency_shared": 2, "max_cycles": 4000000}


def add_cell(root: Path, config: dict, traffic: str, chips: int = 1):
    """Add a configuration and its cell to the tree at ``root`` from
    files and entries alone, weights included."""
    from bench.make_weights import weights
    name = config["name"]
    (root / "bench" / "configs" / f"{name}.json").write_text(
        json.dumps(config))
    (root / "bench" / "weights" / f"{name}.json").write_text(
        json.dumps(weights(config)))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    if name not in {c["name"] for c in bench["configs"]}:
        bench["configs"].append(
            {"name": name, "source": "rehearsal", "reduced": [],
             "file": f"bench/configs/{name}.json", "why": "rehearsal"})
    cell = f"{name}.{traffic}"
    bench["workloads"].append({"name": cell, "config": name,
                               "traffic": traffic, "chips": chips,
                               "why": "rehearsal"})
    # every rehearsal cell reports what the batch cell reports
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m and any(
                    w.endswith(".batch") for w in m["workloads"]):
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)


def make_tiny_root(tmp_path: Path) -> Path:
    """A copy of the benchmark's files with tiny cells added: the paper
    suite at n = 32 as a batch, and three mixed tenants served open
    loop from a traffic file of the rehearsal's own."""
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    peaks = json.loads((tmp_path / "bench" / "peaks.json").read_text())
    peaks["cpu"] = {"hbm_bytes_per_s": 1e10,
                    "source": "placeholder for the CPU rehearsal only"}
    (tmp_path / "bench" / "peaks.json").write_text(json.dumps(peaks))
    (tmp_path / "bench" / "traffic" / "open.json").write_text(json.dumps(
        {"mode": "open", "load": 0.8, "ref_sample": 8,
         "trace_slice_s": 1.0}))
    suite = {"name": "tiny-suite", "n_sm": 2, "machine": TINY_MACHINE,
             "server": {"policy": "bucket", "resident_gmem": True},
             "sizes": {"autocorr": [32], "bitonic": [32], "matmul": [32],
                       "reduction": [32], "transpose": [32]}}
    mixed = {"name": "tiny-mixed", "n_sm": 2, "machine": TINY_MACHINE,
             "server": {"policy": "bucket", "resident_gmem": True,
                        "max_batch": 4},
             "sizes": {"reduction": [32], "autocorr": [32],
                       "transpose": [32]},
             "popularity": {"law": "zipf", "zipf_s": 1.0,
                            "ranking": ["reduction.32", "transpose.32",
                                        "autocorr.32"]},
             "knee_launches_per_s": 20.0}
    add_cell(tmp_path, suite, "batch")
    add_cell(tmp_path, mixed, "open")
    return tmp_path


def run_cell(root, cell, seed=12345, seconds=1.0, trace=0, extra=()):
    """``bench/run.py`` in this process, chip check stubbed; returns
    (exit code, parsed last stdout line or None, stderr text)."""
    from bench import run
    out, err = io.StringIO(), io.StringIO()
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace), *extra],
                  root=root, require_tpu=False, compile_cache=False,
                  out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
