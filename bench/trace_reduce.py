"""From a JAX profiler trace to device busy time, kernel time and gaps.

``extract`` reads the ``.xplane.pb`` the profiler wrote into plain
lists (kept small enough to record as a test fixture); ``reduce``
turns them into the numbers the metric readers use:

* ``busy_s``: the union of the device's op intervals inside the traced
  slice, and ``window_s``, the slice's length;
* ``module_runs``: the runs of XLA programs, from the device's module
  line (one event per run), that lie wholly inside the slice;
* ``device_ops``: device seconds per op name, largest first;
* ``idle_gaps``: idle device seconds by what the host was doing in the
  gap: the innermost span of the program's tracer that covers the
  gap's midpoint, else the benchmark's own annotation, else ``idle``.

Device events are those of ``/device:*`` planes (on a TPU: the
``XLA Ops`` and ``XLA Modules`` lines); a trace without them is an
error.  Only the CPU rehearsal asks for ``host_ops``: there XLA's ops
run on host threads and carry an ``hlo_module`` stat, and those stand
in for device ops, so the rehearsal exercises the same code.  Host annotations are the events named ``bench.*``; the
``bench.mark`` annotation ties the trace's clock to the host's.
"""
from __future__ import annotations

import glob
import os
import warnings


def _stats(ev) -> dict:
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return {k: v for k, v in ev.stats}


def _op_name(name: str) -> str:
    """A TPU op event is named by its whole HLO instruction; keep the
    instruction's name (``%fusion.254 = ...`` -> ``fusion.254``)."""
    return name.split(" = ", 1)[0].lstrip("%") if " = " in name else name


def extract(log_dir: str, host_ops: bool = False) -> dict:
    import jax
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise RuntimeError(f"no profiler trace under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[-1])
    ops, modules, host, cpu_ops = [], [], [], []
    for plane in pd.planes:
        dev = plane.name.startswith("/device:") and \
            not plane.name.startswith("/device:CPU")
        for line in plane.lines:
            for ev in line.events:
                if dev and line.name == "XLA Ops":
                    ops.append([_op_name(ev.name), ev.start_ns,
                                ev.duration_ns])
                elif dev and line.name == "XLA Modules":
                    modules.append([ev.name, ev.start_ns, ev.duration_ns])
                elif not dev and ev.name.startswith("bench."):
                    host.append([ev.name, ev.start_ns, ev.duration_ns])
                elif not dev and ev.duration_ns > 0:
                    st = _stats(ev)
                    if "hlo_module" in st and "hlo_op" in st:
                        cpu_ops.append([_op_name(ev.name), ev.start_ns,
                                        ev.duration_ns, (st["hlo_module"],
                                                         st.get("run_id"))])
    if not ops and not host_ops:
        raise RuntimeError(f"no device ops in the trace under {log_dir}")
    if not ops:        # CPU backend: ops on host threads, runs by run_id
        ops = [[n, s, d] for n, s, d, _ in cpu_ops]
        spans = {}
        for _, s, d, m in cpu_ops:
            a, b = spans.get(m, (s, s + d))
            spans[m] = (min(a, s), max(b, s + d))
        modules = [[m[0], a, b - a] for m, (a, b) in spans.items()]
    return {"ops": ops, "modules": modules, "host": host}


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def span_intervals(tracer, base: float, to_ns) -> list:
    """``(start_ns, end_ns, depth, name)`` of every finished span of the
    program's tracer, whose zero is the host instant ``base``, on the
    trace clock."""
    out = []
    stack = [(r, 0) for r in getattr(tracer, "roots", [])]
    while stack:
        sp, depth = stack.pop()
        if sp.t0 is not None and sp.t1 is not None:
            out.append((to_ns(base + sp.t0), to_ns(base + sp.t1), depth,
                        sp.name))
        stack.extend((c, depth + 1) for c in sp.children)
    return out


def reduce(ex: dict, slice_ns: tuple, spans=()) -> dict:
    """Numbers of the slice ``[lo, hi)`` (trace-clock nanoseconds)."""
    lo, hi = slice_ns
    busy = []
    per_op = {}
    for name, s, d in ex["ops"]:
        a, b = _clip(s, s + d, lo, hi)
        if b > a:
            busy.append((a, b))
            per_op[name] = per_op.get(name, 0.0) + (b - a) * 1e-9
    busy = _union(busy)
    whole = [[name, s, s + d] for name, s, d in ex["modules"]
             if lo <= s and s + d <= hi]
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    host = [(s, s + d, -1, name) for name, s, d in ex["host"]]
    labelled = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        cover = [x for x in spans if x[0] <= mid < x[1]]
        if cover:
            label = max(cover, key=lambda x: x[2])[3]
        else:
            cover = [x for x in host if x[0] <= mid < x[1]]
            label = min(cover, key=lambda x: x[1] - x[0])[3] \
                if cover else "idle"
        labelled[label] = labelled.get(label, 0.0) + (b - a) * 1e-9
    top = lambda d: sorted(([k, v] for k, v in d.items()),
                           key=lambda kv: -kv[1])[:10]
    return {"busy_s": sum(b - a for a, b in busy) * 1e-9,
            "window_s": (hi - lo) * 1e-9,
            "module_runs": whole,
            "device_ops": top(per_op),
            "idle_gaps": top(labelled)}


def clock(ex: dict, mark_perf: float):
    """Host ``perf_counter`` seconds -> trace-clock nanoseconds, from
    the ``bench.mark`` annotation taken at ``mark_perf``."""
    marks = [s for name, s, _ in ex["host"] if name == "bench.mark"]
    if not marks:
        return None
    m = marks[0]
    return lambda t: m + (t - mark_perf) * 1e9

