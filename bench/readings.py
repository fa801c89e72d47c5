"""Shared arithmetic of the metric readers in ``bench/metrics/``."""
from __future__ import annotations


def spans(run, name: str, whole_window: bool = False) -> list:
    """The program's finished spans called ``name`` that started inside
    the window -- before the traced slice, unless ``whole_window``: the
    slice's profiler slows the host -- as ``(start, end, span)`` host
    instants."""
    tr = run.tracer
    if tr is None or not getattr(tr, "roots", None):
        return []
    base = run.span_base
    hi = run.t1 if whole_window or run.slice is None else run.slice[0]
    return [(base + s.t0, base + s.t1, s) for s in tr.find(name)
            if s.t1 is not None and run.t0 <= base + s.t0 <= hi]
