"""The reduction from trace events to busy time, program time and
labelled idle gaps, on a small trace with known answers and on a
recorded slice of a real TPU trace."""
import json
from pathlib import Path

import pytest

from bench import trace_reduce as tr

HERE = Path(__file__).resolve().parent


class _Span:
    def __init__(self, name, t0, t1, children=()):
        self.name, self.t0, self.t1 = name, t0, t1
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


def test_small_trace():
    ex = {"ops": [["a", 100, 50], ["b", 120, 60], ["a", 400, 100],
                  ["c", 900, 300]],
          "modules": [["jit__run_positions(1)", 100, 80],
                      ["jit_other", 400, 100]],
          "host": [["bench.mark", 50, 1], ["bench.submit", 200, 100]]}
    to_ns = tr.clock(ex, mark_perf=10.0)
    assert to_ns(10.0) == 50 and to_ns(10.000001) == pytest.approx(1050)
    # program spans on the host clock (tracer base 10.0 s): a drain from
    # 550 ns to 850 ns on the trace clock with a dispatch inside it
    drain = _Span("drain", 500e-9, 800e-9,
                  [_Span("dispatch", 600e-9, 700e-9)])
    spans = tr.span_intervals(_Tracer([drain]), 10.0, to_ns)
    out = tr.reduce(ex, (100, 1000), spans)
    # busy: [100,180) + [400,500) + [900,1000) = 280 ns of 900
    assert out["busy_s"] == pytest.approx(280e-9)
    assert out["window_s"] == pytest.approx(900e-9)
    assert out["module_runs"] == [["jit__run_positions(1)", 100, 180],
                                  ["jit_other", 400, 500]]
    assert out["device_ops"][0] == ["a", pytest.approx(150e-9)]
    gaps = dict(out["idle_gaps"])
    assert gaps["bench.submit"] == pytest.approx(220e-9)  # [180,400)
    assert gaps["dispatch"] == pytest.approx(400e-9)    # [500,900): mid 700
    assert sum(gaps.values()) == pytest.approx(620e-9)


def test_recorded_tpu_slice():
    """A slice of a TPU v5e trace of the batch cell, as ``extract``
    returned it (trimmed); the reduction's totals must agree with a
    direct count over the same events."""
    path = HERE / "tpu_trace_slice.json"
    rec = json.loads(path.read_text())
    ex = rec["extract"]
    lo, hi = rec["slice_ns"]
    out = tr.reduce(ex, (lo, hi))
    # direct count: sort, merge, sum
    iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d in ex["ops"]
                if min(s + d, hi) > max(s, lo))
    busy, end = 0, lo
    for a, b in iv:
        if b > end:
            busy += b - max(a, end)
            end = b
    assert out["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert all(lo <= a < b <= hi for _, a, b in out["module_runs"])
    assert sum(v for _, v in out["idle_gaps"]) == pytest.approx(
        out["window_s"] - out["busy_s"])


def _roofline():
    import importlib.util
    path = HERE.parent / "metrics" / "smstep_roofline.py"
    spec = importlib.util.spec_from_file_location("roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _roofline_run(dispatches, runs):
    """A run of two launches (1000 and 3000 reference warp-instructions)
    with a slice of [10.2, 10.8] s and the given dispatch spans."""
    from types import SimpleNamespace as NS
    from bench.drive import Launch, Run
    recs = [Launch(0, 0, None, ticket=7, result=object()),
            Launch(1, 1, None, ticket=8, result=object())]
    return Run(mode="batch", seconds=1.0, t0=10.0, t1=11.0,
               items=[NS(weight=1000), NS(weight=3000)], launches=recs,
               machine={"num_read_operands": 3},
               tracer=_Tracer(dispatches), span_base=10.0,
               peaks={"hbm_bytes_per_s": 1e9}, slice=(10.2, 10.8),
               to_ns=lambda t: t * 1e9, trace={"module_runs": runs})


def _dispatch(t0, t1, tickets):
    sp = _Span("dispatch", t0, t1)
    sp.attrs = {"tickets": tickets}
    return sp


def test_roofline_credits_whole_sub_batches_only():
    """A sub-batch whose dispatch lies wholly inside the slice is
    credited its launches' whole reference work over the time of its
    ``_run_positions`` runs; one that starts before the slice is left
    out with its runs, and so are other programs."""
    runs = [["jit__run_positions(1)", 10.3e9, 10.4e9],
            ["jit__run_positions(1)", 10.5e9, 10.6e9],
            ["jit_concatenate(2)", 10.6e9, 10.65e9],
            ["jit__run_positions(1)", 10.7e9, 10.75e9]]
    run = _roofline_run([_dispatch(0.25, 0.66, [7]),
                         _dispatch(0.1, 0.78, [8])], runs)
    # 1000 winstr x 512 B / 1e9 B/s over 0.2 s of kernel
    assert _roofline().read(run) == pytest.approx(
        100 * (1000 * 512 / 1e9) / 0.2)


def test_roofline_reads_nothing_without_a_whole_sub_batch():
    run = _roofline_run([_dispatch(0.1, 0.66, [7])],
                        [["jit__run_positions(1)", 10.3e9, 10.4e9]])
    assert _roofline().read(run) is None


def test_device_ops_are_required_off_the_rehearsal(tmp_path):
    """A trace with no device ops is an error unless the CPU rehearsal
    asks for host ops to stand in."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(jnp.arange(8) + 1)
    jax.profiler.stop_trace()
    with pytest.raises(RuntimeError, match="no device ops"):
        tr.extract(str(tmp_path))
    assert tr.extract(str(tmp_path), host_ops=True)["ops"]
