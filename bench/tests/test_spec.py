"""``BENCHMARK.json`` keeps the contract's names, units and shape, and a
new cell, traffic mix or metric needs new files and entries only."""
import hashlib
import json
import re

from bench.tests.conftest import REPO, add_cell, run_cell

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
ONE_LINE = re.compile(r"^[^\n\t]{1,200}$")


def test_names_and_units():
    from bench.spec import check_names
    assert check_names(BENCH) == []


def test_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert BENCH["paths"] == ["bench"] and 1 <= BENCH["run_seconds"] <= 51
    assert all(ONE_LINE.match(w) for w in BENCH["command"])
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (REPO / c["file"]).exists()
        assert ONE_LINE.match(c["source"]) and ONE_LINE.match(c["why"])
        conf = json.loads((REPO / c["file"]).read_text())
        assert conf["reduced"] == c["reduced"]
        assert all(k in conf for k in c["reduced"])
    pairs = set()
    used = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and ONE_LINE.match(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        assert (REPO / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        assert (REPO / "bench" / "weights" / f"{w['config']}.json").exists()
    assert used == {c["name"] for c in BENCH["configs"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and ONE_LINE.match(m["layer"])
        metrics = REPO / "bench" / "metrics"
        assert (metrics / f"{m['name']}.py").exists() or \
            (metrics / f"{m['name'].split('.')[0]}.py").exists()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert m["better"] in ("lower", "higher")


def test_every_cell_reports_enough():
    from bench.spec import Cell
    for w in BENCH["workloads"]:
        c = Cell(REPO, w["name"])
        names = {m["name"] for m in c.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert c.per_layer
        for m in c.per_layer:        # the metric it moves is reported
            assert m["moves"] in names


def _digest(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()}


def test_new_cell_mix_and_metric_from_files_alone(tiny_root):
    """A throwaway traffic mix, a throwaway per-layer metric and their
    cell are added as new files and new entries; no file changes but
    ``BENCHMARK.json``, and the run reports the new metric."""
    before = _digest(tiny_root / "bench")
    (tiny_root / "bench" / "traffic" / "burst.json").write_text(json.dumps(
        {"mode": "open", "load": 2.0, "ref_sample": 8}))
    (tiny_root / "bench" / "metrics" / "launches_late_ms_max.py").write_text(
        "def read(run):\n"
        "    return max((r.t_sub0 - r.due) * 1e3 for r in run.launches)\n")
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["per_layer"].append(
        {"name": "launches_late_ms_max", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "load generator",
         "moves": "winstr_per_s", "workloads": ["tiny-mixed.burst"]})
    # a quantity split by kind of cell needs no reader of its own
    bench["per_layer"].append(
        {"name": "device_idle_pct.burst", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "device",
         "moves": "winstr_per_s", "workloads": ["tiny-mixed.burst"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    mixed = json.loads(
        (tiny_root / "bench" / "configs" / "tiny-mixed.json").read_text())
    cell = add_cell(tiny_root, mixed, "burst")
    after = _digest(tiny_root / "bench")
    assert all(after[p] == h for p, h in before.items())
    rc, line, err = run_cell(tiny_root, cell, trace=1)
    assert rc == 0 and line["correct"], err
    assert {"launches_late_ms_max", "device_idle_pct.burst"} <= \
        set(line["metrics"])
    rc, line, err = run_cell(tiny_root, cell, trace=0)
    assert set(line["metrics"]) == {"winstr_per_s", "setup_s"}
