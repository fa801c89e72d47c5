"""Every kind of cell at a tiny size on the CPU: a well-formed result."""
import pytest

from bench.tests.conftest import run_cell

CELLS = ["tiny-suite.batch", "tiny-mixed.open"]


def _well_formed(line, trace):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert dev["busy_s"] > 0 and dev["window_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        for group in line["breakdown"].values():
            assert len(group) <= 10


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_is_correct(tiny_root, cell, trace):
    rc, line, err = run_cell(tiny_root, cell, trace=trace)
    assert rc == 0, err
    _well_formed(line, trace)
    assert line["correct"], err
    assert line["attempted"] > 0 and line["failed"] == 0
    from bench.spec import Cell
    c = Cell(tiny_root, cell)
    want = {m["name"] for m in (c.per_layer if trace else c.end_to_end)}
    if trace:            # readers with nothing to read leave a metric out
        assert set(line["metrics"]) <= want and line["metrics"]
    else:
        assert set(line["metrics"]) == want
    assert err.rstrip().splitlines()[-1].startswith("check ")


def test_refuses_without_a_chip(tiny_root):
    from bench import run
    import io
    rc = run.main(["--workload", "tiny-suite.batch", "--seed", "1",
                   "--seconds", "1", "--trace", "0"], root=tiny_root,
                  compile_cache=False, out=io.StringIO(), err=io.StringIO())
    assert rc != 0


def test_same_seed_same_inputs(tiny_root):
    from bench import workload as wl
    from bench.spec import Cell
    c = Cell(tiny_root, "tiny-mixed.open")
    items = wl.load_items(c.config, c.weights)
    share = wl.popularity(c.config, items)
    a = wl.open_schedule(share, 20.0, 3.0, 2**31 + 17)
    b = wl.open_schedule(share, 20.0, 3.0, 2**31 + 17)
    other = wl.open_schedule(share, 20.0, 3.0, 5)
    assert (a[0] == b[0]).all() and (a[1] == b[1]).all()
    # another seed: the same work in another order
    assert sorted(a[1]) == sorted(other[1])
    assert (items[0].inputs(7, 3) == items[0].inputs(7, 3)).all()
