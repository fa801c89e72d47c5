"""The reader of the lane-level share, ``simt_lane_use``.

A traced tiny batch reports it; a program whose ``device-execute`` spans
carry no ``winstr`` reads nothing from them, and does not raise."""
import pytest

from bench.tests.conftest import run_cell
from bench.tests.test_step_metrics import _Run, _Span


def _reader(root):
    from bench.spec import Cell
    return Cell(root, "tiny-suite.batch").reader("simt_lane_use")


def test_traced_batch_reports_simt_lane_use(tiny_root):
    rc, line, err = run_cell(tiny_root, "tiny-suite.batch", trace=1)
    assert rc == 0 and line["correct"], err
    got = line["metrics"]
    assert "simt_lane_use" in got, err
    assert 0 < got["simt_lane_use"]["value"] <= 100


@pytest.mark.parametrize("children", [
    (),
    ({"trips": 10, "useful_steps": 10, "width": 2},),
], ids=["no_groups", "groups_without_winstr"])
def test_reads_nothing_from_an_older_program(tiny_root, children):
    groups = [_Span("device-execute", 1.0, 1.1, a) for a in children]
    old = _Run([_Span("dispatch", 1.0, 2.0, {"tickets": [0]}, groups),
                _Span("dispatch", 5.1, 5.4, {"tickets": [1]})])
    assert _reader(tiny_root)(old) is None


def test_simt_lane_use_counts_idle_lanes(tiny_root):
    """Active lanes over 32 per executed warp-instruction, over every
    group of every dispatch in the window."""
    one = _Span("dispatch", 1.0, 2.0, {"tickets": [0]}, [
        _Span("device-execute", 1.0, 1.1,
              {"winstr": 10, "lanes": 320, "stack_ops": 0}),
        _Span("device-execute", 1.1, 1.2,
              {"winstr": 30, "lanes": 480, "stack_ops": 6})])
    two = _Span("dispatch", 6.5, 7.0, {"tickets": [1]}, [
        _Span("device-execute", 6.5, 6.6,
              {"winstr": 60, "lanes": 960, "stack_ops": 2})])
    run = _Run([one, two])
    assert _reader(tiny_root)(run) == pytest.approx(100.0 * 1760 / (32 * 100))
