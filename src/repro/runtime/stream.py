"""Streams and events — CUDA-style async launch ordering on JAX.

A :class:`Stream` owns a device-resident global memory and a FIFO of
launches against it, exactly like a CUDA stream ordering kernels that
mutate device memory.  ``Stream.launch`` dispatches **eagerly** through
the multi-SM executor and returns a :class:`Launch` future immediately:
JAX's async dispatch keeps the host free, in-stream ordering is real
dataflow (each launch consumes the memory produced by its predecessor),
and nothing touches the host until ``Launch.result`` or an explicit
synchronize.

Cross-stream dependencies use :class:`Event`: ``record_event`` snapshots
the recording stream's tail, ``wait_event`` orders subsequent launches
of the waiting stream after it, and ``Event.gmem()`` exposes the
recorded memory so a consumer stream can *read* the producer's output —
which is the only cross-stream edge that is observable here, since each
stream owns its memory and launches are pure gmem→gmem functions.  The
ordering token threaded by ``wait_event`` is a best-effort device-side
data edge on top of the host's submission order.
"""
from __future__ import annotations

import weakref
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.pipeline import MachineConfig
from ..obs import TRACER
from . import executor as ex
from .registry import Module, ModuleRegistry


def _order_token(arr) -> jnp.ndarray:
    """A zero scalar data-dependent on ``arr`` (device-side ordering edge)."""
    return jnp.min(jnp.ravel(arr)[:1]) & jnp.int32(0)


class Launch:
    """Device-resident future for one kernel launch."""

    def __init__(self, devgrid: ex.DeviceGrid, module: Module, grid,
                 block_dim):
        self._dg = devgrid
        self.module = module
        self.grid = grid
        self.block_dim = block_dim
        self._result: Optional[ex.GridResult] = None

    def gmem(self) -> jnp.ndarray:
        """Final global memory — device array, no host sync."""
        return self._dg.launch_gmem(0)

    def report(self) -> ex.MultiSMReport:
        return self._dg.report()

    def done(self) -> bool:
        g = self.gmem()
        if hasattr(g, "is_ready"):
            return bool(g.is_ready())
        # no readiness probe on this array type: only claim done after
        # actually being done (conservative, never early)
        jax.block_until_ready(g)
        return True

    def wait(self) -> "Launch":
        jax.block_until_ready(self.gmem())
        return self

    def result(self) -> ex.GridResult:
        """Materialize the launch's :class:`GridResult` (host sync)."""
        if self._result is None:
            self._result = self._dg.to_results()[0]
        return self._result


class Event:
    """Snapshot of a stream's tail, for cross-stream ordering and sync.

    ``gmem`` may be None when the recording stream's tail is a queued
    (server-routed) launch whose memory does not exist until its drain
    sub-batch completes: ``query`` stays False until then, and reading
    the event (``gmem()`` / ``token()`` / ``synchronize()``) forces the
    producer to resolve first — the event fires only after its
    producer's sub-batch.
    """

    def __init__(self, gmem: Optional[jnp.ndarray], launches: List):
        self._gmem = gmem
        self._launches = list(launches)

    def gmem(self) -> jnp.ndarray:
        """The recorded stream memory (device array, no sync)."""
        if self._gmem is None:
            self._gmem = self._launches[-1].gmem()
        return self._gmem

    def token(self) -> jnp.ndarray:
        return _order_token(self.gmem())

    def query(self) -> bool:
        """True when every recorded launch has completed (non-blocking)."""
        return all(l.done() for l in self._launches)

    def synchronize(self) -> "Event":
        for l in self._launches:
            l.wait()
        jax.block_until_ready(self.gmem())
        return self


class Stream:
    """In-order launch queue over a stream-owned device global memory."""

    def __init__(self, runtime: "Runtime", gmem=None):
        self._rt = runtime
        self._gmem = None if gmem is None else jnp.asarray(gmem, jnp.int32)
        # only the tail launch is retained (chaining and record_event
        # never look further back) so a long-lived stream does not
        # accumulate one DeviceGrid per launch served
        self._tail: Optional[Launch] = None
        self._token: Optional[jnp.ndarray] = None

    @property
    def gmem(self) -> Optional[jnp.ndarray]:
        """Current stream memory: the last launch's output (device)."""
        return self._gmem

    def set_gmem(self, gmem) -> "Stream":
        self._gmem = jnp.asarray(gmem, jnp.int32)
        return self

    def launch(self, module, grid, block_dim, gmem=None) -> Launch:
        """Enqueue one kernel.  ``gmem=None`` chains on the stream memory
        (CUDA semantics: kernels in a stream see each other's writes);
        an explicit array / :class:`Launch` / :class:`Event` reads that
        memory instead.  Returns immediately with a device future.
        """
        mod = self._rt.registry.as_module(module)
        if gmem is None:
            if self._gmem is None:
                raise ValueError("stream has no memory: pass gmem= or "
                                 "set_gmem() first")
            g = self._gmem
        elif isinstance(gmem, Launch):
            g = gmem.gmem()
        elif isinstance(gmem, Event):
            g = gmem.gmem()
        else:
            g = jnp.asarray(gmem, jnp.int32)
        if self._token is not None:
            g = g + self._token            # ordering edge from wait_event
            self._token = None
        with TRACER.span("stream-launch", module=mod.name,
                         n_blocks=grid[0] * grid[1]):
            dg = ex.execute([ex.LaunchSpec(mod, grid, block_dim, g)],
                            n_sm=self._rt.n_sm, cfg=self._rt.cfg,
                            chunk=self._rt.chunk,
                            registry=self._rt.registry)
        launch = Launch(dg, mod, grid, block_dim)
        self._tail = launch
        self._gmem = launch.gmem()
        return launch

    def record_event(self) -> Event:
        if self._gmem is None:
            raise ValueError("cannot record an event on an empty stream")
        return Event(self._gmem,
                     [self._tail] if self._tail is not None else [])

    def wait_event(self, event: Event) -> "Stream":
        """Order subsequent launches of this stream after ``event``."""
        tok = event.token()
        self._token = tok if self._token is None else self._token + tok
        return self

    def synchronize(self) -> "Stream":
        if self._gmem is not None:
            jax.block_until_ready(self._gmem)
        return self


class QueuedLaunch:
    """Future for a launch queued on a :class:`RuntimeServer`.

    Unlike the eager :class:`Launch` (whose work is already dispatched),
    a queued launch has no result until the server drains the sub-batch
    its drain policy assigned it to.  The server resolves the future the
    moment that sub-batch completes — **exactly once**, whatever order
    the policy ran the window's sub-batches in, and even when a later
    sub-batch of the same drain fails.  ``result``/``gmem``/``wait``
    flush the server when called early; ``done`` never blocks.
    """

    def __init__(self, server, ticket: int, client: str, module: Module,
                 grid, block_dim):
        self._server = server
        self.ticket = ticket
        self.client = client
        self.module = module
        self.grid = grid
        self.block_dim = block_dim
        self._result: Optional[ex.GridResult] = None
        self._error: Optional[BaseException] = None
        self._resolved = False

    def _resolve(self, result: ex.GridResult) -> None:
        if self._resolved:
            raise RuntimeError(
                f"ticket {self.ticket} future resolved twice")
        self._resolved = True
        self._result = result

    def _fail(self, error: BaseException) -> None:
        if self._resolved:
            raise RuntimeError(
                f"ticket {self.ticket} future resolved twice")
        self._resolved = True
        self._error = error

    def done(self) -> bool:
        """Non-blocking: has this launch's sub-batch completed?"""
        return self._resolved

    def result(self) -> ex.GridResult:
        """The launch's :class:`GridResult`; drains the server if needed.

        When a :class:`~repro.runtime.service.ServingLoop` owns the
        server, the future must not drain from this (foreign) thread —
        it waits for the loop to resolve it instead."""
        if not self._resolved:
            loop = getattr(self._server, "_serving_loop", None)
            if loop is not None and loop.running:
                loop.wait_for(self)
            else:
                with self._server.tracer.span(
                        "future-wait", ticket=self.ticket,
                        tenant=self.client):
                    try:
                        self._server.drain()
                    except Exception:
                        # another sub-batch of the drain failed — only
                        # propagate if *our* sub-batch did not complete
                        if not self._resolved:
                            raise
        if self._error is not None:
            raise self._error
        if self._result is None:
            raise RuntimeError(
                f"ticket {self.ticket} did not resolve in drain (queued "
                "behind a failing window? drain again)")
        return self._result

    def gmem(self) -> jnp.ndarray:
        """Final global memory (resolves the future first).

        On a ``resident_gmem`` server the result's memory is already a
        device array and passes through with no host round-trip — so
        chaining a new launch on a resolved future stays device-side
        end to end."""
        return jnp.asarray(self.result().gmem, jnp.int32)

    def wait(self) -> "QueuedLaunch":
        self.result()
        return self


class QueuedStream:
    """In-order launch queue routed through a :class:`RuntimeServer`.

    The server-side sibling of :class:`Stream`: launches enqueue instead
    of dispatching eagerly, and the drain policy may land a stream's
    launches in *different sub-batches* (different gmem buckets).
    Dataflow order survives that: a launch chaining on a still-queued
    predecessor enqueues with a **dependency edge** on it, and the drain
    topologically orders the two sub-batches — producer first, its
    output materialized as the dependent's input just before the
    dependent's group executes.  Nothing flushes at enqueue time: the
    whole chain (plus any other tenants' pending launches) drains in
    one ``drain`` call, in dependency order.  ``record_event`` snapshots
    the tail — before resolution if the tail is still queued, so
    cross-stream consumers observe the event firing only after the
    producer's sub-batch completes.
    """

    def __init__(self, server, gmem=None, client: str = "stream"):
        self._srv = server
        self.client = client
        self._gmem = None if gmem is None else np.asarray(gmem, np.int32)
        self._tail: Optional[QueuedLaunch] = None

    @property
    def gmem(self):
        """Current stream memory (resolves a queued tail first)."""
        if self._tail is not None:
            return self._tail.gmem()
        return self._gmem

    def launch(self, module, grid, block_dim, gmem=None) -> QueuedLaunch:
        """Enqueue one kernel on the server; returns a queued future.

        ``gmem=None`` chains on the stream memory: a still-queued
        predecessor becomes a dependency edge (the server's drain runs
        the producer's sub-batch first and feeds its output in — no
        flush), a resolved one passes its concrete memory.  An explicit
        array / future / :class:`Event` reads that memory instead; a
        still-queued :class:`QueuedLaunch` of the same server is also
        taken as a dependency edge.
        """
        if gmem is None:
            if self._tail is not None:
                g = self._tail          # dependency edge or concrete
            elif self._gmem is not None:
                g = self._gmem
            else:
                raise ValueError("stream has no memory: pass gmem= first")
        elif isinstance(gmem, (Launch, Event)):
            g = np.asarray(gmem.gmem())
        elif isinstance(gmem, QueuedLaunch):
            g = gmem                    # server decides: edge or concrete
        else:
            g = np.asarray(gmem, np.int32)
        fut = self._srv.submit_future(module, grid, block_dim, g,
                                      client=self.client)
        self._tail = fut
        return fut

    def record_event(self) -> Event:
        if self._tail is None and self._gmem is None:
            raise ValueError("cannot record an event on an empty stream")
        if self._tail is None:
            return Event(jnp.asarray(self._gmem, jnp.int32), [])
        # queued tail: the event's memory materializes with the tail's
        # sub-batch; query() stays False until then
        return Event(None, [self._tail])

    def wait_event(self, event: Event) -> "QueuedStream":
        """Order subsequent launches of this stream after ``event``.

        Server submission is host-ordered, so the edge is enforced by
        resolving the event's producers before anything later enqueues.
        """
        event.synchronize()
        return self

    def synchronize(self) -> "QueuedStream":
        if self._tail is not None:
            self._tail.wait()
        return self


class Runtime:
    """The device runtime: one binary cache + config shared by streams.

    >>> rt = Runtime(n_sm=2)
    >>> mod = rt.load(code)
    >>> s = rt.stream(gmem0)
    >>> fut = s.launch(mod, (4, 1), (32, 1))
    >>> out = fut.result().gmem
    """

    def __init__(self, cfg: MachineConfig = MachineConfig(),
                 n_sm: int = 1, chunk: int = 8,
                 registry: Optional[ModuleRegistry] = None):
        self.cfg = cfg
        self.n_sm = n_sm
        self.chunk = chunk
        self.registry = registry or ModuleRegistry(max_modules=1024)
        # weak registry: a stream (and the device memory it pins) is
        # freed as soon as its creator drops it, so a resident runtime
        # serving one stream per request does not leak
        self._streams: "weakref.WeakSet[Stream]" = weakref.WeakSet()

    def load(self, code: np.ndarray, name: Optional[str] = None) -> Module:
        """Load a kernel binary through the content-addressed cache."""
        return self.registry.load(code, name)

    def stream(self, gmem=None) -> Stream:
        s = Stream(self, gmem)
        self._streams.add(s)
        return s

    def synchronize(self) -> "Runtime":
        for s in list(self._streams):
            s.synchronize()
        return self
