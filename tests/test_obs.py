"""Unit + integration suite for :mod:`repro.obs` (PR 7 tentpole).

Pins the observability contracts the runtime now depends on:

* **Exactness** — histogram quantiles are bit-identical to
  ``numpy.percentile`` over the same samples (the BENCH JSON latency
  rows promise exact, not bucket-interpolated, percentiles).
* **Invisibility** — the instrumented drain path is bit-exact with the
  uninstrumented one, and enabling tracing/metrics adds **zero**
  host↔device transfers (``counter_syncs`` unchanged).
* **Lifecycle coverage** — a 3-window dependent drain produces a span
  tree with the full submit → queue-wait → pack → dep-resolve →
  dispatch → device-execute → counter-sync → complete nesting, one
  balanced async begin/end pair per launch, and valid Chrome-trace
  JSON.
* **Shim semantics** — the legacy ``TRANSFERS`` global keeps its
  mutable-int API while the registry counters are the source of truth;
  ``window()`` views are independently zero-based.
* **Edge cases** — empty / single-SM drain ratios are finite
  (``safe_div`` never yields NaN/inf), disabled registries are true
  no-ops.
"""
import json
import math

import numpy as np
import pytest

from repro import obs
from repro import runtime as rt
from repro.core import scheduler
from repro.core.programs import ALL
from repro.obs import jitprof
from repro.runtime.policy import BucketStats
from repro.runtime.server import DrainStats

# --------------------------------------------------------------------------
# small shared workload (shapes shared with the rest of the suite's caches)


def _launch_args(name="bitonic", n=32, gseed=0):
    mod = ALL[name]
    code = mod.build(n)
    grid, bd = mod.launch(n)
    g0 = mod.make_gmem(np.random.default_rng(gseed), n)
    return code, grid, bd, g0


# --------------------------------------------------------------------------
# metrics primitives


def test_histogram_percentiles_exact_vs_numpy():
    rng = np.random.default_rng(7)
    samples = np.abs(rng.normal(0.01, 0.02, size=513)) + 1e-7
    h = obs.Histogram()
    for v in samples:
        h.record(float(v))
    for q in (0, 25, 50, 90, 99, 100):
        assert h.percentile(q) == float(np.percentile(samples, q))
    assert h.count == len(samples)
    assert h.total == pytest.approx(float(samples.sum()))
    st = h.stats()
    assert st["p50"] == float(np.percentile(samples, 50))
    assert st["min"] == float(samples.min())
    assert sum(n for _e, n in st["buckets"]) == len(samples)
    # empty histogram: NaN percentile, but stats stay JSON-safe
    empty = obs.Histogram()
    assert math.isnan(empty.percentile(50))
    json.dumps(empty.stats())


def test_histogram_sample_cap_keeps_counting():
    h = obs.Histogram(max_samples=4)
    for v in (1.0, 2.0, 3.0, 4.0, 100.0, 200.0):
        h.record(v)
    assert h.count == 6                     # bucket table keeps counting
    assert h.percentile(100) == 4.0         # quantiles over retained cap
    assert sum(n for _e, n in h.stats()["buckets"]) == 6
    # the truncation is visible, not silent: stats count the samples the
    # quantiles no longer see, and the text rendering says so
    assert h.dropped_samples == 2
    assert h.stats()["dropped_samples"] == 2
    text = obs.render_snapshot({"histograms": {"h": h.stats()}})
    assert "exclude 2 dropped samples" in text
    # under the cap nothing is dropped and the renderer stays quiet
    h2 = obs.Histogram(max_samples=4)
    h2.record(1.0)
    assert h2.dropped_samples == 0
    assert h2.stats()["dropped_samples"] == 0
    assert "dropped" not in obs.render_snapshot(
        {"histograms": {"h": h2.stats()}})


def test_registry_snapshot_and_family():
    m = obs.MetricsRegistry()
    m.counter("a.x").inc()
    m.counter("a.y").inc(3)
    m.counter("b").inc()
    m.gauge("g").set(2.5)
    m.histogram("h").record(0.25)
    assert m.family("a") == {"x": 1, "y": 3}
    snap = m.snapshot()
    assert snap["counters"] == {"a.x": 1, "a.y": 3, "b": 1}
    assert snap["gauges"] == {"g": 2.5}
    assert snap["histograms"]["h"]["count"] == 1
    json.dumps(snap)                        # JSON-safe end to end
    text = obs.render_snapshot(snap, prefix="  ")
    assert "a.x = 1" in text and "p50" in text
    m.reset()
    assert m.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_disabled_registry_is_noop():
    m = obs.MetricsRegistry(enabled=False)
    m.counter("c").inc(5)
    m.gauge("g").set(1)
    m.histogram("h").record(1.0)
    assert m.counter("c").value == 0
    assert m.histogram("h").count == 0
    assert math.isnan(m.histogram("h").percentile(50))
    assert m.snapshot() == {"counters": {}, "gauges": {}, "histograms": {}}


def test_safe_div_degenerate_denominators():
    assert obs.safe_div(3, 2) == 1.5
    assert obs.safe_div(1, 0) == 0.0
    assert obs.safe_div(1, float("nan")) == 0.0
    assert obs.safe_div(1, float("inf")) == 0.0
    assert obs.safe_div(float("nan"), 1.0) == 0.0


def test_drain_ratio_edge_cases_finite():
    # empty drain: zero makespan must read 0.0, never ZeroDivisionError
    empty = DrainStats(0, 0, 1, 0.0, 0.0, np.zeros(1, np.int64), 0)
    assert empty.duration_balance == 0.0
    # single-SM degenerate: balance is busy/makespan, still finite
    one = empty._replace(n_sm=1, makespan_cycles=10, busy_cycles=7)
    assert one.duration_balance == pytest.approx(0.7)
    assert BucketStats().occupancy == 0.0   # never-dispatched bucket
    b = BucketStats(blocks=3, sm_slots=4)
    assert b.occupancy == pytest.approx(0.75)
    srv = rt.RuntimeServer(n_sm=2)
    _res, stats = srv.drain()               # drain with nothing pending
    for v in (stats.occupancy, stats.duration_balance,
              stats.launches_per_s):
        assert math.isfinite(v)


# --------------------------------------------------------------------------
# TRANSFERS shim


def test_transfers_shim_and_window_views():
    w = rt.TRANSFERS.window()
    assert (w.gmem_uploads, w.gmem_syncs, w.counter_syncs) == (0, 0, 0)
    rt.METRICS.counter("transfers.gmem_uploads").inc()
    assert w.gmem_uploads == 1
    # legacy mutable-int API still lands in the registry counter
    before = rt.METRICS.counter("transfers.counter_syncs").value
    w.counter_syncs += 2
    assert rt.METRICS.counter("transfers.counter_syncs").value == \
        before + 2
    assert w.counter_syncs == 2
    # reset() re-bases this view without disturbing an older one
    w2 = w.window()
    assert w2.gmem_uploads == 0
    w.reset()
    assert w.gmem_uploads == 0 and w2.gmem_uploads == 0
    rt.METRICS.counter("transfers.gmem_uploads").inc()
    assert w.gmem_uploads == 1 and w2.gmem_uploads == 1
    snap = w.snapshot()
    assert set(snap) == {"gmem_uploads", "gmem_syncs", "counter_syncs"}
    with pytest.raises(AttributeError):
        _ = w.not_a_transfer_field


# --------------------------------------------------------------------------
# jit compile attribution


def test_jit_call_fallback_miss_hit(request):
    site = f"test.{request.node.name}"      # unique site: isolated _SEEN
    m = obs.MetricsRegistry()

    def plain(x):                           # no _cache_size probe
        return x + 1

    with obs.jit_call(site, plain, bucket="bA", key=("s", 1), metrics=m):
        plain(1)
    with obs.jit_call(site, plain, bucket="bA", key=("s", 1), metrics=m):
        plain(1)
    with obs.jit_call(site, plain, bucket="bB", key=("s", 2), metrics=m):
        plain(2)
    assert m.counter(f"jit.calls.{site}").value == 3
    assert m.counter("jit.cache_misses").value == 2
    assert m.counter("jit.cache_hits").value == 1
    assert m.counter("jit.cache_misses.bA").value == 1
    assert m.counter("jit.cache_misses.bB").value == 1
    assert m.histogram("jit.trace_ms").count == 2
    summ = jitprof.summary(metrics=m)
    assert summ["bA"]["jit_cache_misses"] == 1
    assert summ["_total"]["jit_cache_misses"] == 2
    d = jitprof.delta(jitprof.summary(metrics=obs.MetricsRegistry()),
                      summ)
    assert d["_total"]["jit_cache_misses"] == 2
    assert "bA" in d and "bB" in d


def test_jit_call_cache_size_probe(request):
    jax = pytest.importorskip("jax")
    site = f"test.{request.node.name}"
    m = obs.MetricsRegistry()
    f = jax.jit(lambda x: x + 1)
    if not hasattr(f, "_cache_size"):
        pytest.skip("jax build exposes no _cache_size probe")
    with obs.jit_call(site, f, bucket="probe", metrics=m):
        f(np.float32(1.0))
    with obs.jit_call(site, f, bucket="probe", metrics=m):
        f(np.float32(2.0))                  # same shape bucket: a hit
    assert m.counter("jit.cache_misses.probe").value == 1
    assert m.counter("jit.cache_hits").value == 1


# --------------------------------------------------------------------------
# span tree + lifecycle tracing through a real dependent drain


@pytest.fixture
def tracer():
    """A server-local tracer, enabled for one test: the server passes it
    down to the executor, so it sees the full nesting, device-execute /
    counter-sync included."""
    tr = obs.Tracer().start()
    yield tr
    tr.stop()


def _dependent_drain(metrics=None, tracer=None):
    """3 chained launches, max_batch=1 → a 3-window dependent drain."""
    code, grid, bd, g0 = _launch_args()
    srv = rt.RuntimeServer(n_sm=2, max_batch=1, metrics=metrics,
                           tracer=tracer)
    f1 = srv.submit_future(code, grid, bd, g0.copy(), client="t0")
    f2 = srv.submit_future(code, grid, bd, f1, client="t1")
    f3 = srv.submit_future(code, grid, bd, f2, client="t1")
    results, stats = srv.drain()
    return srv, (f1, f2, f3), results, stats


def test_span_tree_three_window_dependent_drain(tracer):
    tr = tracer
    m = obs.MetricsRegistry()
    srv, futs, results, stats = _dependent_drain(metrics=m, tracer=tr)
    tr.stop()
    assert stats.n_windows == 3 and stats.n_launches == 3

    # --- submit spans are roots with propagated launch attributes
    submits = tr.find("submit")
    assert len(submits) == 3
    by_ticket = {sp.attrs["ticket"]: sp for sp in submits}
    assert set(by_ticket) == set(results)
    for fut in futs:
        sp = by_ticket[fut.ticket]
        assert sp.attrs["tenant"] == fut.client
        assert sp.attrs["n_blocks"] >= 1
        assert [c.name for c in sp.children] == ["admit"]
        assert sp.t1 is not None and sp.t1 >= sp.t0

    # --- one drain root; windows nest the full serving lifecycle
    drains = [r for r in tr.roots if r.name == "drain"]
    assert len(drains) == 1
    drain = drains[0]
    windows = [c for c in drain.children if c.name == "window"]
    assert len(windows) == 3
    assert drain.attrs["n_launches"] == 3   # set() after exit works
    for i, w in enumerate(windows):
        assert w.attrs["index"] == i
        kids = [c.name for c in w.children]
        for phase in ("pack", "queue-wait", "dep-resolve", "dispatch",
                      "complete"):
            assert phase in kids, (i, phase, kids)
        disp = next(c for c in w.children if c.name == "dispatch")
        assert disp.attrs["n_launches"] == 1
        assert disp.attrs["predicted_cycles"] >= 0
        assert disp.attrs["observed_cycles"] > 0
        # device-execute (executor) nests under dispatch, with the
        # counter-sync host fetch inside the window's extent
        assert tr.find("device-execute", root=disp)
    assert tr.find("counter-sync")

    # --- queue-wait is retroactive: starts at submit, inside drain wall
    for w in windows:
        qw = next(c for c in w.children if c.name == "queue-wait")
        assert qw.t0 <= w.t0 and qw.t1 <= w.t1
        assert qw.attrs["tenant"] in ("t0", "t1")

    # --- async lifecycle: one balanced begin/end pair per launch
    pairs = tr.async_pairs("launch")
    assert set(pairs) == {str(t) for t in results}
    assert all(v == ["b", "e"] for v in pairs.values())

    # --- per-launch latency histograms landed in the server registry
    lat = m.histogram("server.latency_s")
    assert lat.count == 3
    assert m.histogram("server.queue_wait_s").count == 3
    assert m.histogram("server.device_s").count == 3
    for q in (50, 90, 99):
        assert math.isfinite(lat.percentile(q))
    assert m.counter("server.submitted").value == 3
    assert m.gauge("drain.n_windows").value == 3
    assert math.isfinite(m.gauge("drain.duration_balance").value)


def test_chrome_trace_schema(tmp_path, tracer):
    tr = tracer
    _dependent_drain(tracer=tr)
    tr.stop()
    out = tmp_path / "trace.json"
    doc = tr.export(str(out))
    with open(out) as f:
        loaded = json.load(f)               # round-trips through disk
    assert loaded == json.loads(json.dumps(doc))
    events = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms" and events
    for ev in events:
        assert ev["ph"] in ("X", "b", "e", "C")
        assert isinstance(ev["name"], str) and ev["name"]
        assert isinstance(ev["ts"], float) and ev["ts"] >= 0
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        json.dumps(ev["args"])
        if ev["ph"] == "X":
            assert ev["cat"] == "runtime" and ev["dur"] >= 0
        elif ev["ph"] == "C":
            # counter tracks: numeric sample values on their own tid
            assert ev["cat"] == "counter" and ev["tid"] == 3
            assert ev["args"] and all(
                isinstance(v, (int, float)) for v in ev["args"].values())
        else:
            assert ev["cat"] == "launch" and "id" in ev
    # every launch lifecycle is a b/e pair on the async track
    asyncs = [ev for ev in events if ev["ph"] in ("b", "e")]
    assert len(asyncs) == 6
    ids = {ev["id"] for ev in asyncs}
    assert all(sum(1 for ev in asyncs if ev["id"] == i) == 2 for i in ids)
    # drains always publish the standing counter tracks when tracing
    counters = {ev["name"] for ev in events if ev["ph"] == "C"}
    assert {"queue_depth", "device_utilization", "shed_rate"} <= counters


def test_chrome_trace_counters_and_shed_pairs(tmp_path, tracer):
    """Exported trace under deadline shedding: every async b begins an
    e (shed launches close their pair with ``shed=True``), and the
    drain's ``ph:"C"`` counter tracks report the shed in the same
    document (satellite: counter-track schema + shed-path closure)."""
    import time as _time
    tr = tracer
    code, grid, bd, g0 = _launch_args()
    srv = rt.RuntimeServer(n_sm=1, metrics=obs.MetricsRegistry(),
                           tracer=tr)
    doomed = srv.submit_future(code, grid, bd, g0.copy(), client="late",
                               deadline_s=0.0)
    ok = srv.submit_future(code, grid, bd, g0.copy(), client="ontime")
    _time.sleep(0.005)                    # let the deadline expire
    srv.drain()
    tr.stop()
    doc = tr.export(str(tmp_path / "shed-trace.json"))
    events = doc["traceEvents"]
    # both lifecycles closed: two balanced b/e pairs, one flagged shed
    asyncs = [ev for ev in events if ev["ph"] in ("b", "e")]
    by_id = {}
    for ev in asyncs:
        by_id.setdefault(ev["id"], []).append(ev["ph"])
    assert set(by_id) == {str(doomed.ticket), str(ok.ticket)}
    assert all(sorted(v) == ["b", "e"] for v in by_id.values())
    ends = {ev["id"]: ev["args"] for ev in asyncs if ev["ph"] == "e"}
    assert ends[str(doomed.ticket)].get("shed") is True
    assert "shed" not in ends[str(ok.ticket)]
    # the shed also lands on the drain's counter tracks
    shed_samples = [ev for ev in events
                    if ev["ph"] == "C" and ev["name"] == "shed_rate"]
    assert shed_samples and shed_samples[-1]["args"]["shed"] == 1
    util = [ev for ev in events
            if ev["ph"] == "C" and ev["name"] == "device_utilization"]
    assert util and all(
        isinstance(v, (int, float)) for v in util[-1]["args"].values())
    # document round-trips through json (Perfetto-loadable)
    assert json.loads(json.dumps(doc)) == doc


def test_tracer_disabled_records_nothing():
    tr = obs.Tracer()                       # disabled by default
    with tr.span("a", x=1) as sp:
        sp.set(y=2)
    tr.begin_async("launch", 1, "t1")
    tr.end_async("launch", 1)
    tr.timed_span("q", 0.0, 1.0)
    assert tr.roots == [] and tr.async_pairs("launch") == {}
    assert sp is obs.NULL_SPAN
    assert tr.to_chrome()["traceEvents"] == []
    # end without a matching begin after start(): dropped, not an error
    tr.start()
    tr.end_async("launch", 99)
    assert tr.async_pairs("launch") == {}


# --------------------------------------------------------------------------
# invisibility: bit-exactness and zero added transfers


def test_instrumented_path_bit_exact_and_transfer_free():
    code, grid, bd, g0 = _launch_args("autocorr", 32)

    def run(metrics, profile=False):
        srv = rt.RuntimeServer(n_sm=2, metrics=metrics, profile=profile)
        t = [srv.submit(code, grid, bd, g0.copy(), client=f"t{i}")
             for i in range(3)]
        w = rt.TRANSFERS.window()
        results, _stats = srv.drain()
        return [results[k] for k in t], w.snapshot()

    # tracing globally off, metrics disabled
    plain, xfer_plain = run(obs.MetricsRegistry(enabled=False))
    try:
        obs.TRACER.start()
        traced, xfer_traced = run(obs.MetricsRegistry())
        profiled, xfer_prof = run(obs.MetricsRegistry(), profile=True)
    finally:
        obs.TRACER.stop()
        obs.TRACER.clear()
    for a, b, c in zip(plain, traced, profiled):
        np.testing.assert_array_equal(a.gmem, b.gmem)
        np.testing.assert_array_equal(a.cycles_per_block,
                                      b.cycles_per_block)
        np.testing.assert_array_equal(a.op_issues, b.op_issues)
        np.testing.assert_array_equal(a.gmem, c.gmem)
        np.testing.assert_array_equal(a.op_issues, c.op_issues)
    # tracing/metrics on vs off: identical device traffic, and in
    # particular zero extra counter syncs (the tentpole's hard promise)
    assert xfer_traced == xfer_plain
    # the architectural profiler prices host-side counters the drain
    # already fetched — profiling adds zero device transfers too
    assert xfer_prof == xfer_plain


def test_instrumented_matches_sequential_oracle(tracer):
    code, grid, bd, g0 = _launch_args()
    _srv, futs, results, _stats = _dependent_drain(tracer=tracer)
    tracer.stop()
    want = scheduler.run_grid(code, grid, bd, g0.copy())
    np.testing.assert_array_equal(results[futs[0].ticket].gmem,
                                  want.gmem)
    # chained launches re-sort the sorted output: fixed point
    np.testing.assert_array_equal(results[futs[2].ticket].gmem,
                                  want.gmem)
