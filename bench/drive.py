"""Builds the served system from a configuration and drives it.

Set-up builds one ``RuntimeServer`` and warms every compiled shape the
cell's traffic can reach on it; the window then drives that same server
through its normal entry points: ``submit_future`` + ``drain`` for a
batch, ``ServingLoop.submit`` for open-loop arrivals.  Every launch's
instants are taken on the host clock: when it was due, when its submit
call started and returned, and when its future was resolved.
"""
from __future__ import annotations

import contextlib
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import numpy as np

from repro import obs
from repro import runtime as rt
from repro.core.pipeline import MachineConfig

from bench import workload as wl

#: opcodes of the binary format (see ``bench.simt_ref``)
_NOP, _EXIT = 0, 1


@dataclass
class Launch:
    item: int                  # index into the cell's items
    index: int                 # launch number: the input seed's index
    gmem0: np.ndarray          # input memory
    due: float = 0.0           # host instant the launch was due
    ticket: int = -1           # the server's ticket
    t_sub0: float = 0.0        # submit call started
    t_sub1: float = 0.0        # submit call returned
    t_done: Optional[float] = None   # future resolved (either way)
    result: object = None      # GridResult
    error: Optional[BaseException] = None


@dataclass
class Run:
    """What the window did, for the check and the metric readers."""
    mode: str
    seconds: float
    t0: float = 0.0
    t1: float = 0.0
    setup_s: float = 0.0
    launches: list = field(default_factory=list)
    passes: list = field(default_factory=list)
    slice: Optional[tuple] = None     # traced (start, end) host instants
    items: list = field(default_factory=list)
    machine: dict = field(default_factory=dict)
    tracer: object = None
    span_base: float = 0.0            # host instant of the spans' zero
    compiles: list = field(default_factory=list)
    trace: Optional[dict] = None      # bench.trace_reduce result
    to_ns: object = None              # host instant -> trace clock (ns)
    peaks: Optional[dict] = None


def make_server(config: dict, tracer=None) -> rt.RuntimeServer:
    srv = config.get("server", {})
    return rt.RuntimeServer(
        n_sm=int(config["n_sm"]), cfg=MachineConfig(**config["machine"]),
        policy=srv.get("policy", "bucket"),
        resident_gmem=bool(srv.get("resident_gmem", True)),
        max_batch=int(srv.get("max_batch", 32)),
        metrics=obs.MetricsRegistry(),
        tracer=tracer if tracer is not None else obs.Tracer())


def warm_binary(item: wl.Item, salt: int) -> np.ndarray:
    """A binary of the item's length that only exits (``salt`` NOPs
    first, so each item's is distinct): the cheapest launch that hits
    the item's compiled shapes."""
    code = np.zeros_like(item.code)
    code[:, 8] = 7                       # condition T, as emitted
    code[:, 0] = _EXIT
    code[:salt, 0] = _NOP
    return code


def warm(server, items, plan) -> None:
    """Drain ``L`` exit-only launches of each item's footprint and
    geometry for every ``L`` up to ``plan[item]``: each count is its own
    set of compiled shapes in the executor."""
    for i, (item, top) in enumerate(zip(items, plan)):
        code = warm_binary(item, i + 1)
        for L in range(1, int(top) + 1):
            futs = [server.submit_future(
                code, item.grid, item.block_dim,
                np.zeros(item.gmem_len, np.int32), client=f"warm{i}")
                for _ in range(L)]
            server.drain()
            jax.block_until_ready([f.result().gmem for f in futs])


def _settle(fut, rec: Launch) -> None:
    """Record a resolved future's outcome."""
    try:
        rec.result = fut.result()
    except Exception as e:          # the launch failed in the server
        rec.error = e


class Resolver:
    """Stamps the instant each open-loop future resolves.  The future
    has no completion callback, so one thread polls ``done()`` every
    millisecond, as ``ServingLoop.wait_for`` does."""

    def __init__(self):
        self.pending = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench.resolver")
        self._thread.start()

    def add(self, fut, rec: Launch) -> None:
        rec.ticket = fut.ticket
        self.pending.append((fut, rec))

    def _run(self) -> None:
        while not self._stop.is_set():
            now = time.perf_counter()
            n = len(self.pending)          # ``add`` only appends
            left = []
            for fut, rec in self.pending[:n]:
                if fut.done():
                    _settle(fut, rec)
                    rec.t_done = now
                else:
                    left.append((fut, rec))
            self.pending[:n] = left
            time.sleep(0.001)

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()


class Profiler:
    """The JAX profiler around a slice of the window (``--trace 1``).

    The device trace records every XLA op, and the SM step runs about a
    million of them per second of device time; stopping the profiler
    then takes some 40 s.  So the slice is short and sits where its
    stop cannot delay what is measured after it, and the host-span
    readers read only what came before it."""

    #: the device trace starts some time after ``start_trace`` returns;
    #: the slice is counted from this long after it
    SETTLE_S = 0.3

    def __init__(self, log_dir, seconds: float):
        self.log_dir = str(log_dir)
        self.seconds = float(seconds)
        self.state = "off"
        self.start_t = self.stop_t = self.slice_t = None
        self.mark_t = None
        self._timer = None

    def begin(self) -> None:
        """Start tracing now and open the slice once the trace has
        settled; a timer thread closes it ``seconds`` later."""
        self.start()
        time.sleep(min(self.SETTLE_S, self.seconds))
        self.slice_t = time.perf_counter()
        self._timer = threading.Timer(self.seconds, self.stop)
        self._timer.start()

    def begin_later(self, delay: float) -> None:
        """``begin`` off a timer thread, ``delay`` from now (the
        caller's thread is busy driving the window)."""
        self.state = "scheduled"
        self._timer = threading.Timer(delay, self._begin_and_wait)
        self._timer.start()

    def _begin_and_wait(self) -> None:
        self.begin()
        self._timer.join()

    def join(self) -> None:
        """Wait until the slice is closed."""
        if self._timer is not None:
            self._timer.join()

    def start(self):
        jax.profiler.start_trace(self.log_dir)
        self.start_t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.mark"):
            self.mark_t = time.perf_counter()
        self.state = "on"

    def stop(self):
        self.stop_t = time.perf_counter()
        jax.profiler.stop_trace()
        self.state = "done"


def annotate(name: str, on: bool):
    return jax.profiler.TraceAnnotation(name) if on else \
        contextlib.nullcontext()


def _start_spans(run: Run, on: bool) -> None:
    """With tracing on, the program's spans start with the window, from
    a zero taken on the host clock."""
    if on:
        run.span_base = time.perf_counter()
        run.tracer.start()


def run_batch(server, items, seed, seconds, run: Run,
              profiler: Optional[Profiler] = None) -> None:
    """Closed loop: passes of every item once, until ``seconds``.  The
    traced slice opens with the pass that the previous pass's length
    predicts to be the last, so that the pass's first sub-batches run
    wholly inside it, and lasts at most half a pass."""
    on = profiler is not None
    _start_spans(run, on)
    run.t0 = t0 = time.perf_counter()
    k = 0
    while True:
        prev = run.passes[-1][1] - run.passes[-1][0] if run.passes else 0
        if on and profiler.state == "off" and run.passes and \
                time.perf_counter() + prev >= t0 + seconds:
            profiler.seconds = min(profiler.seconds, 0.5 * prev)
            profiler.begin()
        ps = time.perf_counter()
        recs, futs = [], []
        with annotate("bench.submit", on):
            for i, item in enumerate(items):
                rec = Launch(i, k * len(items) + i,
                             item.inputs(seed, k * len(items) + i),
                             due=ps)
                rec.t_sub0 = time.perf_counter()
                fut = server.submit_future(item.code, item.grid,
                                           item.block_dim, rec.gmem0,
                                           client=f"tenant{i}")
                rec.t_sub1 = time.perf_counter()
                rec.ticket = fut.ticket
                recs.append(rec)
                futs.append(fut)
        with annotate("bench.drain", on):
            server.drain()
        with annotate("bench.wait", on):
            for fut, rec in zip(futs, recs):
                if fut.done():
                    _settle(fut, rec)
            jax.block_until_ready([r.result.gmem for r in recs
                                   if r.result is not None])
        pe = time.perf_counter()
        for fut, rec in zip(futs, recs):    # resolved by the pass's end
            if fut.done():
                rec.t_done = pe
        run.launches += recs
        run.passes.append((ps, pe))
        k += 1
        # a traced run whose passes ran longer than predicted takes its
        # slice in one more pass
        if pe - t0 >= seconds and not (on and profiler.state == "off"):
            break
    run.t1 = run.passes[-1][1]
    if on:
        profiler.join()


def run_open(server, items, schedule, seconds, run: Run,
             profiler: Optional[Profiler] = None) -> None:
    """Open loop: each launch is submitted at its due instant through
    ``ServingLoop.submit``, whatever the backlog, and every launch due
    in the window is waited for, up to a minute past its close.  The
    traced slice is the window's last seconds."""
    on = profiler is not None
    loop = rt.ServingLoop(server).start()
    resolver = Resolver()
    try:
        _start_spans(run, on)
        run.t0 = t0 = time.perf_counter() + 0.05
        run.t1 = t1 = t0 + seconds
        if on:
            profiler.seconds = min(profiler.seconds, seconds / 3.0)
            profiler.begin_later(t1 - profiler.seconds
                                 - min(profiler.SETTLE_S, profiler.seconds)
                                 - time.perf_counter())
        for rec in schedule:
            rec.due = t0 + rec.due
            delay = rec.due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            item = items[rec.item]
            rec.t_sub0 = time.perf_counter()
            with annotate("bench.submit", on):
                fut = loop.submit(item.code, item.grid, item.block_dim,
                                  rec.gmem0, client=item.key)
            rec.t_sub1 = time.perf_counter()
            resolver.add(fut, rec)
            run.launches.append(rec)
        delay = t1 - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        limit = t1 + 60.0
        with annotate("bench.wait", on):
            while any(r.t_done is None for r in run.launches) and \
                    time.perf_counter() < limit:
                time.sleep(0.005)
    finally:
        resolver.stop()
        loop.stop(drain=False)
        if on:
            profiler.join()


def open_launches(items, share, rate_hz, seconds, seed) -> list:
    """The cell's schedule with its inputs, built in set-up."""
    due, idx = wl.open_schedule(share, rate_hz, seconds, seed)
    return [Launch(int(i), k, items[int(i)].inputs(seed, k), due=float(d))
            for k, (d, i) in enumerate(zip(due, idx))]
