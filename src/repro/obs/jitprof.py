"""Compile-time attribution around the runtime's ``jax.jit`` seams.

PR 6 proved the serving gap on small hosts is *compile*-bound, not
transfer-bound — but it took a bespoke experiment to learn it.  This
module makes that finding a standing metric: every call into a jitted
seam (:func:`repro.runtime.executor._run_positions`,
:func:`repro.core.pipeline._run_block_jit`) runs under
:func:`jit_call`, which detects whether the call **grew the function's
compiled-trace cache** (a miss: JAX traced, lowered and compiled a new
shape bucket) and attributes the call's wall-milliseconds to the
caller-supplied footprint-bucket label:

* ``jit.cache_misses`` / ``jit.cache_misses.<bucket>`` — counters;
* ``jit.cache_hits`` — counter (dispatch-only calls);
* ``jit.trace_ms`` / ``jit.trace_ms.<bucket>`` — histograms of
  miss-call wall-ms (trace + lower + compile + first execution — the
  number a tenant's first launch into a new shape bucket actually
  pays);
* ``jit.calls.<site>`` — calls per instrumented seam.

Miss detection uses the jitted function's ``_cache_size()`` probe when
JAX provides it (exact, and survives ``jax.clear_caches()``); the
fallback is a per-site seen-key set over the caller's trace key.
Attribution only *times* the call — results are untouched, so the
instrumented path stays bit-exact with the uninstrumented one.

:func:`summary` / :func:`delta` aggregate the per-bucket numbers for
BENCH JSON rows (``jit_trace_ms`` / ``jit_cache_misses`` per bucket).
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, Hashable, Optional

from .metrics import METRICS, MetricsRegistry

#: fallback trace-key memory, per instrumented site (used only when the
#: jitted callable exposes no ``_cache_size`` probe)
_SEEN: Dict[str, set] = {}


@contextmanager
def jit_call(site: str, jitted_fn=None, bucket: str = "default",
             key: Optional[Hashable] = None,
             metrics: Optional[MetricsRegistry] = None, span=None):
    """Time one call into ``jitted_fn`` and attribute a cache miss.

    ``site`` names the seam (metric ``jit.calls.<site>``); ``bucket``
    is the footprint-bucket label misses are attributed to; ``key`` is
    the caller's own trace key, used only when ``jitted_fn`` has no
    ``_cache_size`` probe.  A miss also sets ``jit_miss=True`` on
    ``span``, the caller's enclosing span, when given.  Wrap exactly
    the jitted call::

        with jit_call("executor.run_positions", _run_positions,
                      bucket=label, key=trace_key):
            out = _run_positions(...)
    """
    m = metrics if metrics is not None else METRICS
    size_fn = getattr(jitted_fn, "_cache_size", None)
    before = size_fn() if size_fn is not None else None
    t0 = time.perf_counter()
    yield
    dt_ms = (time.perf_counter() - t0) * 1e3
    if size_fn is not None:
        miss = size_fn() > before
    else:
        seen = _SEEN.setdefault(site, set())
        miss = key not in seen
        seen.add(key)
    m.counter(f"jit.calls.{site}").inc()
    if miss:
        if span is not None:
            span.set(jit_miss=True)
        m.counter("jit.cache_misses").inc()
        m.counter(f"jit.cache_misses.{bucket}").inc()
        m.histogram("jit.trace_ms").record(dt_ms)
        m.histogram(f"jit.trace_ms.{bucket}").record(dt_ms)
    else:
        m.counter("jit.cache_hits").inc()


def summary(metrics: Optional[MetricsRegistry] = None) -> dict:
    """Per-bucket compile attribution so far:
    ``{bucket: {"jit_cache_misses": n, "jit_trace_ms": total_ms}}``
    plus a ``"_total"`` row with hits/misses/trace_ms overall."""
    m = metrics if metrics is not None else METRICS
    out: Dict[str, dict] = {}
    for bucket, misses in m.family("jit.cache_misses").items():
        h = m.histogram(f"jit.trace_ms.{bucket}")
        out[bucket] = {"jit_cache_misses": int(misses),
                       "jit_trace_ms": round(h.total, 3)}
    out["_total"] = {
        "jit_cache_misses": int(m.counter("jit.cache_misses").value),
        "jit_cache_hits": int(m.counter("jit.cache_hits").value),
        "jit_trace_ms": round(m.histogram("jit.trace_ms").total, 3)}
    return out


def delta(before: dict, after: dict) -> dict:
    """Per-bucket difference of two :func:`summary` snapshots, dropping
    buckets that saw no new misses — the per-drain attribution a BENCH
    row carries."""
    out: Dict[str, dict] = {}
    for bucket, vals in after.items():
        prev = before.get(bucket, {})
        d = {k: round(v - prev.get(k, 0), 3) for k, v in vals.items()}
        if bucket == "_total" or d.get("jit_cache_misses"):
            out[bucket] = d
    return out
