"""The readers of the executor's spans and the SM step's loop trips.

A traced tiny batch reports all three; a program whose spans lack the
executor's children and the trip counts reads nothing from them, and
does not raise."""
import pytest

from bench.tests.conftest import run_cell

NEW = ("smstep_us", "smstep_lane_use", "executor_host_ms.tput")


def test_traced_batch_reports_the_step_metrics(tiny_root):
    rc, line, err = run_cell(tiny_root, "tiny-suite.batch", trace=1)
    assert rc == 0 and line["correct"], err
    got = line["metrics"]
    assert {"smstep_lane_use", "executor_host_ms.tput"} <= set(got), err
    assert 0 < got["smstep_lane_use"]["value"] <= 100
    assert got["executor_host_ms.tput"]["value"] > 0
    # both credit the sub-batches the short tiny slice holds whole, and
    # it may hold none
    assert ("smstep_us" in got) == ("smstep_roofline" in got)
    assert got.get("smstep_us", {"value": 1})["value"] > 0


class _Span:
    def __init__(self, name, t0, t1, attrs=None, children=()):
        self.name, self.t0, self.t1 = name, t0, t1
        self.attrs = attrs or {}
        self.children = list(children)


class _Tracer:
    def __init__(self, roots):
        self.roots = roots

    def find(self, name):
        out, stack = [], list(self.roots)
        while stack:
            sp = stack.pop()
            out += [sp] if sp.name == name else []
            stack += sp.children
        return out


class _Run:
    """A window of one sub-batch, as a program that predates the
    executor's spans records it: ``dispatch`` with no children."""
    t0, t1, span_base, slice = 0.0, 10.0, 0.0, (5.0, 6.0)
    launches = []
    trace = {"module_runs": [["jit__run_positions", 5.2e9, 5.3e9]],
             "busy_s": 0.5, "window_s": 1.0}

    def __init__(self, roots):
        self.tracer = _Tracer(roots)

    @staticmethod
    def to_ns(t):
        return t * 1e9


@pytest.mark.parametrize("metric", NEW)
def test_readers_read_nothing_from_an_older_program(tiny_root, metric):
    from bench.spec import Cell
    read = Cell(tiny_root, "tiny-suite.batch").reader(metric)
    old = _Run([_Span("dispatch", 1.0, 2.0, {"tickets": [0]}),
                _Span("dispatch", 5.1, 5.4, {"tickets": [1]})])
    assert read(old) is None


def test_lane_use_counts_padded_slots(tiny_root):
    from bench.spec import Cell
    read = Cell(tiny_root, "tiny-suite.batch").reader("smstep_lane_use")
    groups = [_Span("device-execute", 1.0, 1.1,
                    {"trips": 10, "useful_steps": 10, "width": 2}),
              _Span("device-execute", 1.1, 1.2,
                    {"trips": 4, "useful_steps": 6, "width": 2})]
    run = _Run([_Span("dispatch", 1.0, 2.0, {"tickets": [0]}, groups)])
    assert read(run) == pytest.approx(100.0 * 16 / 28)
